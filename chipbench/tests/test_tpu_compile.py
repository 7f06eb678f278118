"""The fat-tree cell's device refine compiles for a described TPU v5e,
on one chip and sharded over a 2x2 host, at the cell's sizes (k=48,
512 ranks).  No chip is needed: the TPU compiler compiles for a topology
that is described and not attached."""
import functools

import numpy as np
import pytest

K = 48
N_HOSTS = K ** 3 // 4
RANKS = 512
STACK = 8
WIDTH = 12          # the CSR row width the program pads npb_dt(512) to


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _shapes(sharding_p, sharding_rep):
    import jax
    import jax.numpy as jnp
    S = jax.ShapeDtypeStruct
    return (S((STACK, RANKS), jnp.int32, sharding=sharding_p),
            S((RANKS, WIDTH), jnp.int32, sharding=sharding_rep),
            S((RANKS, WIDTH), jnp.float32, sharding=sharding_rep),
            S((1, 1), jnp.float32, sharding=sharding_rep),
            (S((N_HOSTS, 3), jnp.float32, sharding=sharding_rep),
             S((N_HOSTS,), jnp.float32, sharding=sharding_rep)),
            S((), jnp.int32, sharding=sharding_rep))


@pytest.fixture(autouse=True)
def _pallas(monkeypatch):
    # the kernels pick Pallas by asking which backend runs, and here that
    # is the CPU: steer them in the test, as the chip would
    from repro.kernels.hop_dist import ops
    monkeypatch.setattr(ops, "_resolve", lambda impl: "pallas")


def _refine(sortless):
    from repro.core import mapping_jax
    return functools.partial(mapping_jax._refine_one, movers=64,
                             total_passes=16, dense=False,
                             dims=("fattree",), scale=1.0,
                             sortless=sortless)


def test_fattree_refine_compiles_for_one_v5e(topo):
    import jax
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(jax.vmap(_refine(False),
                          in_axes=(0, None, None, None, None, None)))
    text = fn.lower(*_shapes(one, one)).compile().as_text()
    assert "tpu_custom_call" in text


def test_fattree_refine_compiles_sharded_over_a_2x2_host(topo):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices[:4]), ("dev",))
    batched = jax.vmap(_refine(True),
                       in_axes=(0, None, None, None, None, None))
    fn = jax.jit(jax.shard_map(batched, mesh=mesh,
                               in_specs=(P("dev"), P(), P(), P(), P(), P()),
                               out_specs=P("dev"), check_vma=False))
    compiled = fn.lower(*_shapes(NamedSharding(mesh, P("dev")),
                                 NamedSharding(mesh, P()))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
