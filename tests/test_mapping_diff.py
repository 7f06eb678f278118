"""Differential equivalence: vectorized mapping kernels vs retained loop
reference implementations.

The vectorized hot-path kernels (``_pairwise_refine``, ``bisect_graph``,
``select_nodes``, ``greedy_placement``) must produce placements whose
quality (hop-bytes / cut weight) is equal or better than the scalar-loop
references on seeded random guests, torus and fat-tree hosts, with and
without faults.  ``select_nodes`` and ``greedy_placement`` are
decision-identical by construction, so they are held to exact equality.
"""
import numpy as np
import pytest

from repro.core import mapping as mp
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.fattree import FatTreeTopology
from repro.core.topology import TorusTopology
from repro.workloads.patterns import lammps_like, npb_dt_like

# absorbs float-associativity noise between incremental and re-summed costs
RTOL = 1 + 1e-9


def _random_guest(n: int, seed: int, density: float = 0.3) -> np.ndarray:
    rng = np.random.default_rng(seed)
    W = rng.random((n, n)) * (rng.random((n, n)) < density)
    W = W + W.T
    np.fill_diagonal(W, 0.0)
    return W


def _hosts():
    return [
        ("torus", TorusTopology((4, 4, 4))),
        ("fattree", FatTreeTopology(8)),
    ]


def _weights(topo, seed: int, faulty: bool) -> np.ndarray:
    if not faulty:
        return topo.hop_matrix()
    p_f = np.zeros(topo.n_nodes)
    bad = np.random.default_rng(seed).choice(topo.n_nodes, 6, replace=False)
    p_f[bad] = 0.1
    return topo.weight_matrix(p_f)


@pytest.mark.parametrize("seed", range(6))
def test_bisect_graph_cut_not_worse(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 48))
    W = _random_guest(n, seed + 100)
    size0 = int(rng.integers(1, n))
    vec = mp.bisect_graph(W, size0, rng=np.random.default_rng(1))
    ref = mp.bisect_graph_reference(W, size0, rng=np.random.default_rng(1))
    assert vec.sum() == size0 == ref.sum()
    assert mp.cut_weight(W, vec) <= mp.cut_weight(W, ref) * RTOL


@pytest.mark.parametrize("faulty", [False, True])
@pytest.mark.parametrize("host_name,topo", _hosts())
def test_select_nodes_identical(host_name, topo, faulty):
    D = _weights(topo, seed=11, faulty=faulty)
    for count in (5, 16, 31):
        vec = mp.select_nodes(D, count)
        ref = mp.select_nodes_reference(D, count)
        assert np.array_equal(vec, ref), f"{host_name} count={count}"


@pytest.mark.parametrize("wl_fn,n", [(npb_dt_like, 40), (lammps_like, 27)])
@pytest.mark.parametrize("host_name,topo", _hosts())
def test_greedy_placement_identical(host_name, topo, wl_fn, n):
    wl = wl_fn(n)
    D = topo.hop_matrix()
    vec = mp.greedy_placement(wl.comm.G_v, np.arange(topo.n_nodes), D)
    ref = mp.greedy_placement_reference(wl.comm.G_v, np.arange(topo.n_nodes), D)
    assert np.array_equal(vec, ref)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("faulty", [False, True])
@pytest.mark.parametrize("host_name,topo", _hosts())
def test_refine_hop_bytes_not_worse(host_name, topo, faulty, seed):
    n = 48
    G = _random_guest(n, seed)
    D = _weights(topo, seed=seed + 50, faulty=faulty)
    start = np.random.default_rng(seed).choice(topo.n_nodes, n, replace=False)
    vec = mp._pairwise_refine(G, D, start)
    ref = mp._pairwise_refine_reference(G, D, start)
    hb_vec = mp.hop_bytes(G, D, vec)
    hb_ref = mp.hop_bytes(G, D, ref)
    # the refiner only accepts improving swaps: never worse than its input
    assert hb_vec <= mp.hop_bytes(G, D, start) * RTOL
    assert hb_vec <= hb_ref * RTOL, f"{host_name} faulty={faulty} seed={seed}"
    # a swap-refined placement stays a valid assignment
    assert len(set(vec.tolist())) == n


@pytest.mark.parametrize("wl_fn,n", [(npb_dt_like, 40), (lammps_like, 27)])
@pytest.mark.parametrize("faulty", [False, True])
@pytest.mark.parametrize("host_name,topo", _hosts())
def test_map_graph_end_to_end_not_worse(host_name, topo, wl_fn, n, faulty):
    """Full-pipeline differential: vectorized map_graph vs the loop stack."""
    wl = wl_fn(n)
    D = _weights(topo, seed=9, faulty=faulty)
    coords = topo.coords_array()
    nodes = np.arange(topo.n_nodes)
    vec = mp.map_graph(wl.comm.G_v, nodes, coords, D=D,
                       rng=np.random.default_rng(0))
    with mp.use_reference_impl():
        ref = mp.map_graph(wl.comm.G_v, nodes, coords, D=D,
                           rng=np.random.default_rng(0))
    hb_vec = mp.hop_bytes(wl.comm.G_v, D, vec)
    hb_ref = mp.hop_bytes(wl.comm.G_v, D, ref)
    assert len(set(vec.tolist())) == n
    assert hb_vec <= hb_ref * RTOL, (
        f"{host_name} {wl_fn.__name__} faulty={faulty}: "
        f"{hb_vec:.6e} > {hb_ref:.6e}")


@pytest.mark.parametrize("faulty", [False, True])
def test_tofa_policy_end_to_end_not_worse(faulty):
    """Engine-level differential: the full TOFA pipeline, Eq. 1 weighted."""
    topo = TorusTopology((4, 4, 4))
    wl = npb_dt_like(24, seed=5)
    p_f = None
    if faulty:
        p_f = np.zeros(topo.n_nodes)
        p_f[np.random.default_rng(3).choice(topo.n_nodes, 6,
                                            replace=False)] = 0.05
    req = PlacementRequest(comm=wl.comm, topology=topo, p_f=p_f)
    vec = PlacementEngine().place(req, policy="tofa",
                                  rng=np.random.default_rng(0))
    with mp.use_reference_impl():
        ref = PlacementEngine().place(req, policy="tofa",
                                      rng=np.random.default_rng(0))
    assert vec.hop_bytes <= ref.hop_bytes * RTOL


def test_use_reference_impl_restores():
    vec_fns = {name: getattr(mp, name) for name in mp._VECTORIZED_IMPL}
    with mp.use_reference_impl():
        assert mp.bisect_graph is mp.bisect_graph_reference
        assert mp.select_nodes is mp.select_nodes_reference
        assert mp.greedy_placement is mp.greedy_placement_reference
        assert mp._pairwise_refine is mp._pairwise_refine_reference
    for name, fn in vec_fns.items():
        assert getattr(mp, name) is fn


def test_swap_threshold_matches_the_device_refine():
    """The NumPy refine loops and the device refine accept a swap at the
    same gain: ``mapping.GAIN_EPS`` is the value of
    ``kernels.swap_gain.ref.GAIN_EPS``, the device refine's definition
    (a NumPy-only install cannot import it, so both are kept)."""
    pytest.importorskip("jax")
    from repro.kernels.swap_gain import ref

    assert mp.GAIN_EPS == ref.GAIN_EPS


def test_hop_bytes_batch_matches_scalar():
    rng = np.random.default_rng(0)
    topo = TorusTopology((4, 4))
    D = topo.hop_matrix()
    G = _random_guest(10, 1)
    P = np.stack([rng.choice(16, 10, replace=False) for _ in range(5)])
    batch = mp.hop_bytes_batch(G, D, P)
    scalar = [mp.hop_bytes(G, D, p) for p in P]
    np.testing.assert_allclose(batch, scalar, rtol=1e-12)
    # blocked path (tiny block budget forces multiple gathers)
    blocked = mp.hop_bytes_batch(G, D, P, max_block_elems=120)
    np.testing.assert_allclose(blocked, scalar, rtol=1e-12)


def test_comm_graph_builders_match_loop_semantics():
    """Vectorized scatter accumulation == sequential add_p2p loops."""
    from repro.core.comm_graph import CommGraph, _ring_pairs

    ranks = [3, 0, 7, 5, 2]
    g = len(ranks)
    vec = CommGraph(8)
    vec.add_all_reduce(ranks, 640.0, repeats=2.0)
    vec.add_all_reduce(ranks, 64.0, algorithm="recursive_doubling")
    vec.add_all_gather(ranks, 100.0)
    vec.add_reduce_scatter(ranks, 500.0)
    vec.add_all_to_all(ranks, 500.0, repeats=3.0)
    vec.add_broadcast(ranks, 80.0, root=2)
    vec.add_collective_permute([(0, 1), (1, 0), (5, 2)], 50.0)

    ref = CommGraph(8)
    per_pair = 2.0 * (g - 1) / g * 640.0
    for a, b in _ring_pairs(ranks):
        ref.add_p2p(a, b, per_pair * 2.0, 2 * (g - 1) * 2.0)
    k = 1
    while k < g:
        for idx, r in enumerate(ranks):
            peer = idx ^ k
            if peer < g and idx < peer:
                ref.add_p2p(r, ranks[peer], 64.0, 1.0)
        k <<= 1
    for a, b in _ring_pairs(ranks):
        ref.add_p2p(a, b, (g - 1) * 100.0, g - 1)
    for a, b in _ring_pairs(ranks):
        ref.add_p2p(a, b, (g - 1) / g * 500.0, g - 1)
    chunk = 500.0 / g
    for i in range(g):
        for j in range(i + 1, g):
            ref.add_p2p(ranks[i], ranks[j], 2 * chunk * 3.0, 2 * 3.0)
    order = list(range(g))
    order[0], order[2] = order[2], order[0]
    k = 1
    while k < g:
        for idx in range(k):
            peer = idx + k
            if peer < g:
                ref.add_p2p(ranks[order[idx]], ranks[order[peer]], 80.0, 1.0)
        k <<= 1
    for s, d in [(0, 1), (1, 0), (5, 2)]:
        ref.add_p2p(s, d, 50.0, 1.0)

    np.testing.assert_allclose(vec.G_v, ref.G_v, rtol=1e-12)
    np.testing.assert_allclose(vec.G_m, ref.G_m, rtol=1e-12)


def test_comm_graph_two_rank_ring_duplicate_pairs():
    """g=2 ring: the two directed ring edges hit the same unordered pair —
    np.add.at must accumulate both, like two sequential add_p2p calls."""
    from repro.core.comm_graph import CommGraph
    vec = CommGraph(4)
    vec.add_all_reduce([1, 3], 100.0)
    per_pair = 2.0 * 1 / 2 * 100.0
    assert vec.G_v[1, 3] == vec.G_v[3, 1] == 2 * per_pair


def test_heatmap_binning_matches_dense_scatter():
    wl = lammps_like(64)
    m = wl.comm.G_v
    n, bins = 64, 32
    idx = np.arange(n) * bins // n
    dense = np.zeros((bins, bins))
    np.add.at(dense, (idx[:, None].repeat(n, 1), idx[None, :].repeat(n, 0)), m)
    sparse = np.zeros((bins, bins))
    i, j = np.nonzero(m)
    np.add.at(sparse, (idx[i], idx[j]), m[i, j])
    np.testing.assert_allclose(sparse, dense)
    hm = wl.comm.heatmap(width=bins)
    assert len(hm.splitlines()) == bins


def test_engine_shared_cache_reuses_tofa_candidates():
    topo = TorusTopology((4, 4, 4))
    p_f = np.zeros(topo.n_nodes)
    p_f[[0, 5]] = 0.1
    engine = PlacementEngine()
    wl = npb_dt_like(20, seed=2)
    req = PlacementRequest(comm=wl.comm, topology=topo, p_f=p_f)
    engine.place(req, policy="tofa")
    assert engine.stats["shared_misses"] == 1
    engine.place(req, policy="tofa")
    stats = engine.cache_stats()
    assert stats["shared_hits"] >= 1
    # a different health snapshot must not reuse the memo
    p2 = p_f.copy()
    p2[9] = 0.2
    req2 = PlacementRequest(comm=wl.comm, topology=topo, p_f=p2)
    engine.place(req2, policy="tofa")
    assert engine.cache_stats()["shared_misses"] == 2
