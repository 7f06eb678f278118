"""``chip_smoke.py`` phases at small sizes, on the host's jax backend.

On the chip the script runs these phases at the paper's deployment size;
here they run on tiny topologies (lazy threshold lowered so the implicit
paths are taken) to check their control flow and their own checks.  The
Pallas check is the chip's alone: off TPU the kernels resolve to their
jitted references.  The script itself must refuse to run without a TPU.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

import chip_smoke  # noqa: E402
from repro.core.fattree import FatTreeTopology  # noqa: E402
from repro.core.topology import TorusTopology  # noqa: E402
from repro.workloads.patterns import (alltoall_heavy, lammps_like,  # noqa: E402
                                      npb_dt_like)


def test_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_JAX_DEVICES", "0")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                   # no result line
    assert "no TPU" in out.err


def test_service_phase_small():
    row = chip_smoke.run_service(dims=(4, 4, 4), n_req=24, rate=5.0,
                                 n_flaky=6)
    assert chip_smoke.check_service(row) == []
    assert row["placements"] >= row["requests"] - row["statuses"].get(
        "shed", 0)
    assert row["churn_events"] > 0


@pytest.mark.parametrize("case", ["dense-torus", "implicit-torus",
                                  "implicit-fattree"])
def test_placement_phase_small(case):
    """At float64 the jax placements are numpy's own, so the hop-bytes
    gate sits at exactly zero excess."""
    if case == "dense-torus":
        topo, wl = TorusTopology((4, 4, 4)), alltoall_heavy(32)
        state = chip_smoke._faulty_state(topo.n_nodes, 4, seed=1)
    elif case == "implicit-torus":
        topo, wl, state = TorusTopology((4, 4, 8)), lammps_like(32), None
    else:
        topo, wl = FatTreeTopology(8), npb_dt_like(24)
        state = chip_smoke._faulty_state(topo.n_nodes, 4, seed=2)
    row = chip_smoke.run_placement(topo, wl, state, reps=2,
                                   lazy_threshold=64)
    assert chip_smoke.check_placement(row, want_pallas=False) == []
    assert row["dtype"] == "float64"
    assert row["identical_share"] == 1.0
    assert max(np.abs(row["hop_bytes_rel_to_numpy"])) == 0.0


def test_sharded_phase_checks():
    rows = {"s": {"bit_identical": False,
                  "devices1": {"sharded_dispatches": 0},
                  "devices4": {"sharded_dispatches": 0}}}
    msgs = chip_smoke.check_sharded(rows, 4)
    assert len(msgs) == 2
    assert any("differ" in m for m in msgs)
    assert any("did not increase" in m for m in msgs)
