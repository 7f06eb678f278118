"""The benchmark's plain reference against the program's own arithmetic
on small fabrics, and its generators against the originals they copy."""
import numpy as np
import pytest

from chipbench import generator, reference, run

from .conftest import REPO

TORI = [(4, 4, 4), (3, 4, 5), (8, 8)]


def _fabric(spec):
    return run.Bench(REPO).module("fabrics", spec["kind"]).Reference(spec)


def _faults(n, k, seed):
    rng = np.random.default_rng(seed)
    p = np.zeros(n)
    p[rng.choice(n, k, replace=False)] = 0.02
    return p


@pytest.mark.parametrize("dims", TORI)
def test_torus_route_weights_match_the_program(dims):
    from repro.core.topology import TorusTopology
    topo = TorusTopology(dims)
    p = _faults(topo.n_nodes, 5, 1)
    fab = _fabric({"kind": "torus", "dims": list(dims)})
    ids = np.arange(topo.n_nodes)
    W = fab.weights(ids[:, None], ids[None, :], p > 0)
    np.testing.assert_array_equal(W, topo.weight_matrix(p))


@pytest.mark.parametrize("k", [4, 8])
def test_fattree_weights_match_the_program(k):
    from repro.core.fattree import FatTreeTopology
    topo = FatTreeTopology(k)
    p = _faults(topo.n_nodes, 6, 2)
    fab = _fabric({"kind": "fattree", "k": k})
    ids = np.arange(topo.n_nodes)
    W = fab.weights(ids[:, None], ids[None, :], p > 0)
    np.testing.assert_array_equal(W, topo.weight_matrix(p))


@pytest.mark.parametrize("spec", [{"kind": "torus", "dims": [4, 4, 4]},
                                  {"kind": "fattree", "k": 8}])
def test_hop_bytes_and_block_baseline_match_the_program(spec):
    from repro.core import backend, mapping
    from repro.core.fattree import FatTreeTopology
    from repro.core.topology import TorusTopology
    topo = (TorusTopology(tuple(spec["dims"])) if spec["kind"] == "torus"
            else FatTreeTopology(spec["k"]))
    fab = _fabric(spec)
    p = _faults(topo.n_nodes, 4, 3)
    W = topo.weight_matrix(p)
    G = generator.npb_dt(21).G_v
    rng = np.random.default_rng(4)
    allocatable = np.ones(topo.n_nodes, dtype=bool)
    allocatable[:3] = False
    block = reference.block_placement(21, allocatable)
    np.testing.assert_array_equal(block, np.arange(3, 24))
    with backend.use("numpy"):
        for place in (rng.permutation(topo.n_nodes)[:21], block):
            M = reference.sym_weights(fab, place, p > 0)
            assert reference.hop_bytes(G, M) == mapping.hop_bytes(G, W,
                                                                  place)


def test_swap_gains_are_the_hop_bytes_a_swap_saves():
    fab = _fabric({"kind": "torus", "dims": [4, 4, 4]})
    penal = _faults(64, 3, 5) > 0
    G = generator.lammps(12).G_v + generator.npb_dt(12, seed=3).G_v
    place = np.random.default_rng(6).permutation(64)[:12]
    M = reference.sym_weights(fab, place, penal)
    gains = reference.swap_gains(G, M)
    base = reference.hop_bytes(G, M)
    for i in range(12):
        for j in range(12):
            q = place.copy()
            q[i], q[j] = q[j], q[i]
            after = reference.hop_bytes(G, reference.sym_weights(fab, q,
                                                                 penal))
            assert gains[i, j] == pytest.approx(base - after, rel=1e-12,
                                                abs=1e-3)
    gap = reference.swap_gap(G, fab, place, penal)
    assert gap == pytest.approx(max(0.0, gains.max()) / base)


def test_invalid_reasons():
    alloc = np.ones(8, dtype=bool)
    alloc[7] = False
    ok = np.array([0, 1, 2])
    assert reference.invalid_reason(ok, 3, alloc) is None
    assert "two ranks" in reference.invalid_reason(np.array([0, 0, 1]), 3,
                                                   alloc)
    assert "allocatable" in reference.invalid_reason(np.array([0, 1, 7]), 3,
                                                     alloc)
    assert "ranks" in reference.invalid_reason(ok, 4, alloc)


@pytest.mark.parametrize("n", [16, 64, 85, 512])
def test_guest_copies_match_the_program_generators(n):
    from repro.workloads.patterns import lammps_like, npb_dt_like
    np.testing.assert_array_equal(generator.lammps(n).G_v,
                                  lammps_like(n).comm.G_v)
    np.testing.assert_array_equal(generator.npb_dt(n).G_m,
                                  npb_dt_like(n).comm.G_m)
