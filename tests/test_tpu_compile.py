"""The hot path's Pallas kernels compile for a TPU v5e that is described,
not attached.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; only the chip's own compiler says whether it accepts them —
scalar stores to vector memory, block shapes off the (8, 128) tiling and
scoped-VMEM overflows are refused there and nowhere else.  Every compile
here is at float32, the dtype the jax backend runs on TPU.

The topology is described inside a module fixture: one process at a time
may load the TPU compiler's library, so nothing here touches it at
import or collection time.  The persistent compilation cache is off for
these compiles (an entry for a described chip cannot be read back).
"""
import functools

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

F32 = jnp.float32
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("n_pad", [256, 1024])
def test_swap_select_compiles_vmapped(one_chip, n_pad):
    """As the refine loop calls it: vmapped over the candidate axis, the
    guest matrix shared."""
    from repro.kernels.swap_gain.kernel import swap_select_tpu

    B = 4
    fn = jax.vmap(swap_select_tpu, in_axes=(0, None, 0, 0, None))
    text = _compiled_text(
        fn, _shape(one_chip, (B, n_pad, n_pad), F32),
        _shape(one_chip, (n_pad, n_pad), F32),
        _shape(one_chip, (B, n_pad), F32), _shape(one_chip, (B,), I32),
        _shape(one_chip, (), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("metric", ["torus", "fattree"])
def test_hop_dist_compiles(one_chip, metric):
    from repro.kernels.hop_dist.kernel import fattree_hop_tpu, torus_hop_tpu

    m = k = 1024
    fn = (functools.partial(torus_hop_tpu, dims=(16, 16, 32))
          if metric == "torus" else fattree_hop_tpu)
    text = _compiled_text(fn, _shape(one_chip, (m, 3), F32),
                          _shape(one_chip, (k, 3), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_pen", [1, 16, 32])
def test_penalised_torus_hop_compiles(one_chip, n_pen):
    """The faulty torus's kernel at Titan's shape (25x16x24x2): the
    penalised table and its neighbour table in SMEM, the Pallas call
    named ``torus_hop``."""
    from repro.kernels.hop_dist.kernel import torus_hop_tpu

    def fn(cu, cv, pen):
        return torus_hop_tpu(cu, cv, (25, 16, 24, 2), pen)

    text = _compiled_text(fn, _shape(one_chip, (512, 4), F32),
                          _shape(one_chip, (512, 4), F32),
                          _shape(one_chip, (4, n_pen), F32))
    assert "tpu_custom_call" in text and "torus_hop" in text


@pytest.mark.parametrize("case", ["dense", "implicit-torus",
                                  "implicit-fattree", "implicit-faulty-torus"])
def test_refine_step_compiles_with_pallas(one_chip, monkeypatch, case):
    """One whole jitted candidate-stack refine, with ``impl="auto"``
    steered to the Pallas kernels as it resolves on a TPU host: the dense
    guest through ``swap_select``, the implicit metrics through
    ``hop_dist``."""
    from repro.core import mapping_jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, n, k = 4, 256, 8
    if case == "dense":
        dense, dims, k = True, None, n
        Ds = _shape(one_chip, (512, 512), F32)
    elif case == "implicit-torus":
        dense, dims = False, (16, 16, 32)
        Ds = (_shape(one_chip, (8192, 3), F32), _shape(one_chip, (3, 0), F32))
    elif case == "implicit-faulty-torus":
        dense, dims = False, (25, 16, 24, 2)
        Ds = (_shape(one_chip, (19200, 4), F32),
              _shape(one_chip, (4, 16), F32))
    else:
        dense, dims = False, ("fattree",)
        Ds = (_shape(one_chip, (5488, 3), F32),
              _shape(one_chip, (5488,), F32))
    G_dense = (n, n) if dense else (1, 1)
    # a fresh jit (not the lru-cached one), so no trace made for the host
    # platform by an earlier test can be reused
    run = mapping_jax._refine_jit.__wrapped__(64, 16, dense, dims, 2.0)
    text = run.lower(
        _shape(one_chip, (B, n), I32), _shape(one_chip, (n, k), I32),
        _shape(one_chip, (n, k), F32), _shape(one_chip, G_dense, F32), Ds,
        _shape(one_chip, (), I32)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case", ["implicit-fattree", "implicit-faulty-torus"])
def test_subproblem_refine_compiles_with_pallas(one_chip, monkeypatch, case):
    """One multilevel level's sub-problems as one program: a stack of 128
    guests of up to 8 ranks, each lane with its own sparse rows and
    valid count, the implicit metric shared, ``hop_dist`` in every lane."""
    from repro.core import mapping_jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S, n, k = 128, 8, 4
    if case == "implicit-faulty-torus":
        dims = (25, 16, 24, 2)
        Ds = (_shape(one_chip, (19200, 4), F32),
              _shape(one_chip, (4, 16), F32))
    else:
        dims = ("fattree",)
        Ds = (_shape(one_chip, (27648, 3), F32),
              _shape(one_chip, (27648,), F32))
    run = mapping_jax._refine_jit_subproblems.__wrapped__(n, 16, dims, 1.0)
    text = run.lower(
        _shape(one_chip, (S, n), I32), _shape(one_chip, (S, n, k), I32),
        _shape(one_chip, (S, n, k), F32), _shape(one_chip, (1, 1), F32), Ds,
        _shape(one_chip, (S,), I32)).compile().as_text()
    assert "tpu_custom_call" in text
