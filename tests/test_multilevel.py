"""Multilevel mapping + lazy-distance tests (PR 7).

Pins the three contracts of the scaling stack:

* ``LazyDistance`` is *bit-identical* to the dense Eq. 1 weight matrix on
  every index form — implicitness is a memory optimisation, never a
  quality change.
* ``tofa-ml`` degrades to flat ``tofa`` exactly below the coarsening
  threshold, and stays within 5% hop-bytes of it above.
* The engine's lazy path (above ``lazy_threshold``) places end-to-end
  without ever materialising an O(N^2) matrix, and its LRU caches evict
  with counters.
"""
import numpy as np
import pytest

from conftest import hypothesis_or_stubs

from repro.core import backend as core_backend
from repro.core import mapping, multilevel
from repro.core.comm_graph import CommGraph
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.fattree import FatTreeTopology
from repro.core.lazydist import (FatTreeLazyDistance, TorusLazyDistance,
                                 is_lazy)
from repro.core.topology import TorusTopology
from repro.workloads.patterns import npb_dt_like

given, settings, st = hypothesis_or_stubs()


def _faults(n_nodes, n_faulty, seed=7, p=0.02):
    p_f = np.zeros(n_nodes)
    if n_faulty:
        bad = np.random.default_rng(seed).choice(n_nodes, n_faulty,
                                                 replace=False)
        p_f[bad] = p
    return p_f


# --------------------------------------------------------------- lazy metric
@pytest.mark.parametrize("dims", [(4, 3, 5), (5, 5), (2, 3, 4, 3)])
@pytest.mark.parametrize("n_faulty,straggle", [(0, False), (5, False),
                                               (5, True), (0, True)])
def test_torus_lazy_bitexact(dims, n_faulty, straggle):
    topo = TorusTopology(dims)
    N = topo.n_nodes
    p_f = _faults(N, n_faulty)
    s = None
    if straggle:
        s = np.zeros(N)
        s[[1, N // 2]] = 0.5
    dense = topo.weight_matrix(p_f, c=1.0, straggler=s)
    lazy = topo.lazy_distance(p_f, straggler=s)
    assert is_lazy(lazy) and lazy.shape == (N, N)
    # full row-block / ix_ / broadcast / scalar forms, all bit-equal
    rows = np.asarray(lazy[np.arange(N)])
    np.testing.assert_array_equal(rows, dense)
    sub = np.random.default_rng(0).choice(N, 7, replace=False)
    np.testing.assert_array_equal(np.asarray(lazy[np.ix_(sub, sub)]),
                                  dense[np.ix_(sub, sub)])
    np.testing.assert_array_equal(
        np.asarray(lazy[sub[:, None], sub[None, :]]),
        dense[np.ix_(sub, sub)])
    np.testing.assert_array_equal(np.asarray(lazy[3]), dense[3])
    assert lazy[2, 5] == dense[2, 5]


def test_fattree_lazy_bitexact():
    topo = FatTreeTopology(8)
    N = topo.n_nodes
    for p_f in (None, _faults(N, 6)):
        dense = topo.weight_matrix(p_f)
        lazy = topo.lazy_distance(p_f)
        assert isinstance(lazy, FatTreeLazyDistance)
        np.testing.assert_array_equal(np.asarray(lazy[np.arange(N)]), dense)


def test_lazy_never_silently_densifies():
    lazy = TorusTopology((4, 4, 4)).lazy_distance()
    with pytest.raises(TypeError):
        np.asarray(lazy)
    with pytest.raises(TypeError):
        np.array(lazy)


def test_implicit_spec_only_when_uniform():
    """The torus exposes its in-kernel spec in every health state whose
    links are priced uniformly by hops and the fault term: healthy (an
    empty penalised table) or faulty (the penalised coordinates, padded
    to a power of two with -1).  Stragglers price links by slowdown, so
    a straggling torus has no spec."""
    topo = TorusTopology((4, 4, 4))
    healthy = topo.lazy_distance().implicit
    assert healthy is not None and healthy.penalised.shape == (3, 0)
    p_f = _faults(64, 3)
    faulty = topo.lazy_distance(p_f).implicit
    assert faulty is not None and faulty.penalised.shape == (3, 4)
    coords = topo.coords_array()
    np.testing.assert_array_equal(faulty.penalised[:, :3],
                                  coords[p_f > 0].T)
    assert (faulty.penalised[:, 3] == -1).all()
    s = np.zeros(64)
    s[5] = 1.0
    assert topo.lazy_distance(straggler=s).implicit is None
    assert topo.lazy_distance(p_f, straggler=s).implicit is None
    spec = topo.lazy_distance(p_f=np.zeros(64)).implicit
    assert spec is not None and spec.dims == (4, 4, 4)


def test_hop_matrix_memoised_construction_cheap():
    topo = TorusTopology((6, 6, 6))
    assert "_hop_matrix" not in topo.__dict__  # deferred until first use
    M = topo.hop_matrix()
    assert topo.hop_matrix() is M
    ft = FatTreeTopology(8)
    assert ft.hop_matrix() is ft.hop_matrix()


# ------------------------------------------------------- coarsen / uncoarsen
def test_coarsen_conserves_sizes_and_weight():
    G = npb_dt_like(300, seed=3).comm.weights("volume")
    levels, Gc, sizes_c = multilevel.coarsen(G, 160)
    assert levels and Gc.shape[0] <= 160
    assert sizes_c.sum() == 300
    assert Gc.sum() <= G.sum() + 1e-9          # matched weight internalised
    assert np.allclose(Gc, Gc.T) and np.all(np.diag(Gc) == 0)
    # every original process lands in exactly one final super-vertex
    labels = multilevel.uncoarsen_map(levels)
    assert len(labels[-1]) == 300
    counts = np.bincount(labels[-1], minlength=Gc.shape[0])
    np.testing.assert_array_equal(counts, sizes_c)


def test_coarsen_noop_below_target():
    G = npb_dt_like(64, seed=3).comm.weights("volume")
    levels, Gc, sizes_c = multilevel.coarsen(G, 160)
    assert levels == [] and Gc.shape[0] == 64


@given(n=st.integers(min_value=2, max_value=40), seed=st.integers(0, 10))
@settings(max_examples=25, deadline=None)
def test_coarsen_roundtrip_property(n, seed):
    rng = np.random.default_rng(seed)
    G = rng.random((n, n))
    G = (G + G.T) / 2
    np.fill_diagonal(G, 0.0)
    match, Gc, sizes_c = multilevel.coarsen_level(
        G, np.ones(n, dtype=np.int64))
    nc = Gc.shape[0]
    assert match.min() >= 0 and match.max() == nc - 1
    assert sizes_c.sum() == n and sizes_c.max() <= 2  # HEM pairs at most
    # coarse edge (a, b) equals the sum of fine edges crossing a-b
    for a in range(min(nc, 4)):
        for b in range(min(nc, 4)):
            if a == b:
                continue
            fa, fb = match == a, match == b
            assert Gc[a, b] == pytest.approx(G[np.ix_(fa, fb)].sum())


# ---------------------------------------------------------------- multilevel
def test_multilevel_noop_is_map_graph():
    topo = TorusTopology((5, 5, 5))
    G = npb_dt_like(100, seed=3).comm.weights("volume")
    D = topo.hop_matrix()
    nodes = np.arange(100)   # len(nodes) == n: no snake pre-truncation
    a = multilevel.multilevel_map(G, nodes, topo.coords_array(), D=D,
                                 rng=np.random.default_rng(0),
                                 coarse_target=160)
    b = mapping.map_graph(G, nodes, topo.coords_array(), D=D,
                          rng=np.random.default_rng(0))
    np.testing.assert_array_equal(a, b)  # coarsening no-op -> bit-identical


@pytest.mark.parametrize("topo,n,n_faulty", [
    (TorusTopology((8, 8, 8)), 256, 0),
    (TorusTopology((8, 8, 8)), 256, 12),
    (TorusTopology((8, 8, 8)), 512, 0),
    (FatTreeTopology(8), 100, 6),
    (FatTreeTopology(8), 128, 0),
])
def test_tofa_ml_within_5pct_of_flat(topo, n, n_faulty):
    p_f = _faults(topo.n_nodes, n_faulty)
    wl = npb_dt_like(n, seed=3)
    req = PlacementRequest(comm=wl.comm, topology=topo, p_f=p_f)
    engine = PlacementEngine()
    flat = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    ml = engine.place(req, policy="tofa-ml", rng=np.random.default_rng(0))
    assert ml.hop_bytes <= flat.hop_bytes * 1.05


def test_tofa_ml_bit_identical_below_coarse_target():
    topo = TorusTopology((8, 8, 4))
    wl = npb_dt_like(120, seed=3)  # 120 <= COARSE_TARGET=160
    req = PlacementRequest(comm=wl.comm, topology=topo,
                           p_f=_faults(topo.n_nodes, 8))
    engine = PlacementEngine()
    flat = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    ml = engine.place(req, policy="tofa-ml", rng=np.random.default_rng(0))
    np.testing.assert_array_equal(ml.placement, flat.placement)


def test_hierarchical_select_contract():
    topo = TorusTopology((8, 8, 8))
    p_f = _faults(topo.n_nodes, 20)
    D = topo.lazy_distance(p_f)
    groups = topo.hierarchy_groups(64)
    healthy = p_f == 0
    sel = multilevel.hierarchical_select(D, groups, 100, healthy=healthy)
    assert len(sel) == 100
    assert len(np.unique(sel)) == 100
    assert healthy[sel].all()
    np.testing.assert_array_equal(sel, np.sort(sel))
    # quality: the hierarchical ball's internal cost stays close to the
    # dense full-matrix select_nodes ball's
    Wd = topo.weight_matrix(p_f)
    ref = mapping.select_nodes(
        Wd + 1e9 * ((p_f[:, None] > 0) | (p_f[None, :] > 0)), 100)
    cost = lambda ids: Wd[np.ix_(ids, ids)].sum()
    assert cost(sel) <= cost(ref) * 1.4   # rack-granular ball, bounded loss


# ----------------------------------------------------------- engine, caches
def test_engine_lazy_end_to_end_matches_dense():
    topo = TorusTopology((6, 6, 4))   # 144 nodes
    wl = npb_dt_like(64, seed=3)
    for n_faulty in (0, 8):
        req = PlacementRequest(comm=wl.comm, topology=topo,
                               p_f=_faults(topo.n_nodes, n_faulty))
        dense_eng = PlacementEngine(lazy_threshold=10_000)
        lazy_eng = PlacementEngine(lazy_threshold=100)
        assert not dense_eng._use_lazy(topo)
        assert lazy_eng._use_lazy(topo)
        assert is_lazy(lazy_eng.hops(topo))
        d = dense_eng.place(req, policy="tofa", rng=np.random.default_rng(0))
        l = lazy_eng.place(req, policy="tofa", rng=np.random.default_rng(0))
        assert l.hop_bytes <= d.hop_bytes * 1.05


def test_engine_lazy_threshold_env(monkeypatch):
    monkeypatch.setenv("REPRO_LAZY_THRESHOLD", "123")
    assert PlacementEngine().lazy_threshold == 123
    assert PlacementEngine(lazy_threshold=9).lazy_threshold == 9


def test_engine_lru_topology_eviction():
    engine = PlacementEngine(max_cached_topologies=2)
    wl = npb_dt_like(16, seed=3)
    for dims in [(4, 4), (4, 5), (4, 6), (4, 7)]:
        req = PlacementRequest(comm=wl.comm, topology=TorusTopology(dims))
        engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    stats = engine.cache_stats()
    assert stats["topology_evictions"] >= 2
    assert stats["cached_topologies"] <= 2


def test_engine_lru_weight_eviction():
    topo = TorusTopology((4, 4, 4))
    engine = PlacementEngine(max_cached_weights=1)
    wl = npb_dt_like(16, seed=3)
    for seed in range(3):
        req = PlacementRequest(comm=wl.comm, topology=topo,
                               p_f=_faults(topo.n_nodes, 4, seed=seed))
        engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    stats = engine.cache_stats()
    assert stats["weight_evictions"] >= 1
    assert stats["cached_weight_matrices"] <= 1


# ------------------------------------------------------------- jax implicit
@pytest.mark.skipif(not core_backend.has_jax(), reason="jax not installed")
def test_jax_implicit_matches_dense():
    topo = TorusTopology((6, 6, 4))
    n = 64
    G = npb_dt_like(n, seed=3).comm.weights("volume")
    Dd = topo.hop_matrix()
    Dl = topo.lazy_distance()
    assert Dl.implicit is not None
    rng = np.random.default_rng(0)
    P = np.stack([rng.permutation(topo.n_nodes)[:n] for _ in range(4)])
    hb_np = mapping.hop_bytes_batch(G, Dd, P)
    R_np = mapping.refine_batch(G, Dd, P)
    with core_backend.use("jax"):
        hb_dense = mapping.hop_bytes_batch(G, Dd, P)
        hb_impl = mapping.hop_bytes_batch(G, Dl, P)
        R_dense = mapping.refine_batch(G, Dd, P)
        R_impl = mapping.refine_batch(G, Dl, P)
    np.testing.assert_allclose(hb_dense, hb_np, rtol=1e-9)
    np.testing.assert_allclose(hb_impl, hb_np, rtol=1e-9)
    np.testing.assert_array_equal(R_dense, R_np)
    np.testing.assert_array_equal(R_impl, R_np)


def test_numpy_lazy_refine_matches_dense():
    topo = TorusTopology((6, 6, 4))
    n = 48
    G = npb_dt_like(n, seed=3).comm.weights("volume")
    p_f = _faults(topo.n_nodes, 6)
    Dd = topo.weight_matrix(p_f)
    Dl = topo.lazy_distance(p_f)   # faulty -> no implicit spec, exact lazy
    rng = np.random.default_rng(0)
    P = np.stack([rng.permutation(topo.n_nodes)[:n] for _ in range(3)])
    np.testing.assert_allclose(mapping.hop_bytes_batch(G, Dl, P),
                               mapping.hop_bytes_batch(G, Dd, P), rtol=1e-12)
    np.testing.assert_array_equal(mapping.refine_batch(G, Dl, P),
                                  mapping.refine_batch(G, Dd, P))


@pytest.mark.parametrize("dims", [(4, 3, 5, 2), (3, 4, 3)])
def test_torus_route_extra_vectorised_equals_scalar_walk(dims):
    """The lazy adapter prices all flagged pairs of a request in one
    vectorised walk, ``==`` to a scalar walk of ``route_nodes`` per pair,
    under faults (adjacent ones included) and stragglers."""
    from repro.core.topology import FAULT_PENALTY
    topo = TorusTopology(dims)
    n = topo.n_nodes
    p_f = np.zeros(n)
    p_f[[0, 1, 3, 4, 17]] = 0.02
    slow = np.zeros(n)
    slow[[2, 9, 30]] = [0.37, 1.25, 0.5]
    for straggler in (None, slow):
        lazy = topo.lazy_distance(p_f, c=2.0, straggler=straggler)
        u, v = (a.ravel() for a in np.meshgrid(np.arange(n), np.arange(n),
                                               indexing="ij"))
        got = lazy._route_extra(u, v)
        s = np.zeros(n) if straggler is None else straggler
        want = np.zeros(n * n)
        for i, (a0, b0) in enumerate(zip(u, v)):
            nodes = topo.route_nodes(int(a0), int(b0))
            for a, b in zip(nodes[:-1], nodes[1:]):
                if p_f[a] > 0 or p_f[b] > 0:
                    want[i] += 2.0 * FAULT_PENALTY
                elif s[a] > 0 or s[b] > 0:
                    want[i] += 2.0 * max(s[a], s[b])
        assert (got == want).all()
