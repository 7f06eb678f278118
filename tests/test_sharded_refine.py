"""Sharded candidate-stack refine: device axis + single-vs-sharded identity.

The real assertions run in a subprocess because the XLA host-device count
is frozen the moment jax initialises — ``--xla_force_host_platform_device_
count=8`` must be in ``XLA_FLAGS`` *before* the first jax import, which a
test process that already imported jax (conftest, earlier tests) cannot
undo.  The child script exercises:

* ``backend.use("jax")`` sees 8 devices, ``devices=1`` pins the
  single-device vmap path, ``REPRO_JAX_DEVICES`` caps it;
* sharded ``refine_many`` (shard_map over the candidate axis) returns
  placements **bit-identical** to the single-device vmap dispatch — on
  dense, implicit-torus, implicit-fat-tree and implicit faulty-torus
  distances — including
  ragged stacks that need edge-padding to a device multiple;
* the ``sharded_dispatches`` stat increments only on the sharded path.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytest.importorskip("jax")

REPO = Path(__file__).resolve().parents[1]

CHILD = r"""
import numpy as np
from repro.core import backend, mapping_jax
from repro.core.fattree import FatTreeTopology
from repro.core.topology import TorusTopology
from repro.workloads.patterns import npb_dt_like

be = backend.get_backend("jax")
assert be.device_count == 8, be.device_count
with backend.use("jax", devices=1) as b1:
    assert b1.device_count == 1
with backend.use("jax", devices=3) as b3:
    assert b3.device_count == 3

wl = npb_dt_like(40)
G = wl.comm.G_v
rng = np.random.default_rng(0)

torus = TorusTopology((4, 4, 4))
ft = FatTreeTopology(8)
p_f = np.zeros(ft.n_nodes)
p_f[rng.choice(ft.n_nodes, 6, replace=False)] = 0.1
p_t = np.zeros(torus.n_nodes)
p_t[[0, 1, 5, 21, 42]] = 0.02          # 0 and 1 adjacent
cases = [
    ("dense", torus.hop_matrix(), torus.n_nodes),
    ("implicit-torus", torus.lazy_distance(), torus.n_nodes),
    ("implicit-fattree", ft.lazy_distance(p_f, c=2.0), ft.n_nodes),
    ("implicit-faulty-torus", torus.lazy_distance(p_t), torus.n_nodes),
]
for b in (3, 8, 16):     # ragged (pad to device multiple), 1/lane, 2/lane
    for name, D, n_nodes in cases:
        P = np.stack([rng.permutation(n_nodes)[:40] for _ in range(b)])
        with backend.use("jax", devices=1):
            single = mapping_jax.refine_many(G, D, P)
        with backend.use("jax") as bj:
            before = bj.stats["sharded_dispatches"]
            sharded = mapping_jax.refine_many(G, D, P)
            assert bj.stats["sharded_dispatches"] == before + 1, name
        assert sharded.shape == P.shape, (name, b)
        assert np.array_equal(single, sharded), (name, b)
print("OK")
"""


def test_sharded_refine_bit_identical():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env.pop("REPRO_JAX_DEVICES", None)
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip().endswith("OK")


def test_devices_cap_env(monkeypatch):
    """REPRO_JAX_DEVICES caps the dispatch without an explicit argument
    (resolved per backend construction, not frozen at import)."""
    from repro.core import backend

    monkeypatch.setenv("REPRO_JAX_DEVICES", "1")
    be = backend.get_backend("jax")
    assert be.devices == 1
    assert be.device_count == 1
    monkeypatch.delenv("REPRO_JAX_DEVICES")
    assert backend.get_backend("jax").devices == 0
