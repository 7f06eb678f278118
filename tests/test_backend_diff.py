"""Differential equivalence: numpy backend vs jit-compiled jax backend.

The jax backend (:mod:`repro.core.mapping_jax`) is a decision-identical
port of the vectorized NumPy mapping kernels: at the float64 dtype policy
and with integer-weight guests (every in-tree workload except the
fractional all-reduce edges of ``lammps_like``), float64 arithmetic on
the kernels' integer inputs is exact, so the jitted kernels accept the
same swaps in the same order and placements match **bit for bit** on
torus and fat-tree hosts, healthy and faulty.  Guests with non-dyadic
weights may round differently inside BLAS/XLA reductions, so they are
held to quality tolerance instead.

Also covered: the dtype policy (float64 default, float32 opt-in;
placements integer-exact on every backend), the numpy-only fallback
guarantees, and ``place_many`` ≡ sequential ``place``.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import backend, mapping
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.fattree import FatTreeTopology
from repro.core.topology import TorusTopology
from repro.workloads.patterns import halo3d, lammps_like, npb_dt_like

RTOL = 1e-9


def _hosts():
    return [("torus", TorusTopology((4, 4, 4))),
            ("fattree", FatTreeTopology(8))]


def _weights(topo, faulty: bool, seed: int = 5) -> np.ndarray:
    if not faulty:
        return topo.hop_matrix()
    p_f = np.zeros(topo.n_nodes)
    bad = np.random.default_rng(seed).choice(topo.n_nodes, 6, replace=False)
    p_f[bad] = 0.1
    return topo.weight_matrix(p_f)


def _request(topo, n: int, faulty: bool) -> PlacementRequest:
    wl = npb_dt_like(n)
    p_f = None
    if faulty:
        p_f = np.zeros(topo.n_nodes)
        bad = np.random.default_rng(5).choice(topo.n_nodes, 6,
                                              replace=False)
        p_f[bad] = 0.1
    return PlacementRequest(comm=wl.comm, topology=topo, p_f=p_f)


# ------------------------------------------------------------- hop bytes
@pytest.mark.parametrize("host_name,topo", _hosts())
@pytest.mark.parametrize("faulty", [False, True])
def test_hop_bytes_parity(host_name, topo, faulty):
    wl = npb_dt_like(40)
    D = _weights(topo, faulty)
    rng = np.random.default_rng(0)
    P = np.stack([rng.permutation(topo.n_nodes)[:40] for _ in range(5)])
    ref = mapping.hop_bytes_batch(wl.comm.G_v, D, P)
    with backend.use("jax"):
        out = mapping.hop_bytes_batch(wl.comm.G_v, D, P)
        one = mapping.hop_bytes(wl.comm.G_v, D, P[0])
    np.testing.assert_allclose(out, ref, rtol=RTOL)
    np.testing.assert_allclose(one, ref[0], rtol=RTOL)


# ------------------------------------------------- kernel-level identity
@pytest.mark.parametrize("host_name,topo", _hosts())
@pytest.mark.parametrize("faulty", [False, True])
def test_refine_identical(host_name, topo, faulty):
    wl = npb_dt_like(40)
    D = _weights(topo, faulty)
    rng = np.random.default_rng(1)
    P = np.stack([rng.permutation(topo.n_nodes)[:40] for _ in range(3)])
    ref = mapping.refine_batch(wl.comm.G_v, D, P)
    with backend.use("jax") as be:
        before = be.stats["numpy_fallbacks"]
        out = mapping.refine_batch(wl.comm.G_v, D, P)
        single = mapping._pairwise_refine(wl.comm.G_v, D, P[0])
        assert be.stats["numpy_fallbacks"] == before    # served by jax
    assert np.array_equal(out, ref), f"{host_name} faulty={faulty}"
    assert np.array_equal(single, ref[0])


@pytest.mark.parametrize("health", ["healthy", "faults", "stragglers", "both"])
def test_fattree_lazy_refine_identical(health):
    """Fat-tree implicit path: the jitted refine computes the endpoint-form
    fat-tree metric in-kernel (coords + penalty gather, never a stored
    matrix) for *every* health state, and stays bit-identical to the NumPy
    kernels running against the lazy adapter's ``__getitem__``."""
    from repro.core import mapping_jax

    topo = FatTreeTopology(8)
    p_f = strag = None
    if health in ("faults", "both"):
        p_f = np.zeros(topo.n_nodes)
        bad = np.random.default_rng(5).choice(topo.n_nodes, 6, replace=False)
        p_f[bad] = 0.1
    if health in ("stragglers", "both"):
        strag = np.zeros(topo.n_nodes)
        slow = np.random.default_rng(9).choice(topo.n_nodes, 5, replace=False)
        strag[slow] = 1.5
    Dl = topo.lazy_distance(p_f, c=2.0, straggler=strag)
    assert mapping_jax.lazy_supported(Dl), health
    wl = npb_dt_like(40)
    rng = np.random.default_rng(1)
    P = np.stack([rng.permutation(topo.n_nodes)[:40] for _ in range(3)])
    ref = mapping.refine_batch(wl.comm.G_v, Dl, P)
    hb_ref = mapping.hop_bytes_batch(wl.comm.G_v, Dl, ref)
    with backend.use("jax") as be:
        before = be.stats["numpy_fallbacks"]
        out = mapping.refine_batch(wl.comm.G_v, Dl, P)
        hb = mapping.hop_bytes_batch(wl.comm.G_v, Dl, out)
        assert be.stats["numpy_fallbacks"] == before    # served by jax
    assert np.array_equal(out, ref), health
    np.testing.assert_allclose(hb, hb_ref, rtol=RTOL)


@pytest.mark.parametrize("dims", [(4, 4, 4), (4, 3, 5, 2)])
def test_faulty_lazy_torus_places_on_device(dims, monkeypatch):
    """A faulty torus above the lazy threshold is a device path: tofa's
    refines and scores run the jitted kernels with the penalised torus
    metric in-kernel, nothing falls back to NumPy, and at float64 the
    placement is the NumPy backend's own."""
    from repro.core import mapping_jax

    topo = TorusTopology(dims)
    req = _request(topo, 24, faulty=True)
    calls = []
    real = mapping_jax.refine_many

    def counted(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    ref = PlacementEngine(backend="numpy", lazy_threshold=0).place(
        req, policy="tofa", rng=np.random.default_rng(0))
    monkeypatch.setattr(mapping_jax, "refine_many", counted)
    engine = PlacementEngine(backend="jax", lazy_threshold=0)
    with backend.use("jax") as be:
        before = be.stats["numpy_fallbacks"]
        out = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
        assert be.stats["numpy_fallbacks"] == before
        assert be.dtype == "float64"
    assert calls
    np.testing.assert_array_equal(out.placement, ref.placement)
    assert out.hop_bytes_fault_weighted == ref.hop_bytes_fault_weighted


@pytest.mark.parametrize("host_name,topo", _hosts())
def test_select_nodes_identical(host_name, topo):
    W = _weights(topo, faulty=True)
    for count in (5, 17, 33):
        ref = mapping.select_nodes(W, count)
        with backend.use("jax"):
            out = mapping.select_nodes(W, count)
        assert np.array_equal(out, ref), count
        with backend.use("jax"):
            seeded = mapping.select_nodes(W, count, seed=3)
        assert np.array_equal(seeded, mapping.select_nodes(W, count, seed=3))


@pytest.mark.parametrize("host_name,topo", _hosts())
@pytest.mark.parametrize("wl_fn", [npb_dt_like, lammps_like])
def test_greedy_placement_identical(host_name, topo, wl_fn):
    wl = wl_fn(24)
    D = topo.hop_matrix()
    ref = mapping.greedy_placement(wl.comm.G_v, np.arange(topo.n_nodes), D)
    with backend.use("jax"):
        out = mapping.greedy_placement(wl.comm.G_v, np.arange(topo.n_nodes),
                                       D)
    assert np.array_equal(out, ref)


# ------------------------------------------------- engine-level identity
@pytest.mark.parametrize("host_name,topo", _hosts())
@pytest.mark.parametrize("faulty", [False, True])
@pytest.mark.parametrize("policy", ["linear", "greedy", "topo", "tofa"])
def test_policy_placements_identical(host_name, topo, faulty, policy):
    """Integer-weight guests: fixed seeds give bit-identical placements."""
    req = _request(topo, 24, faulty)
    ref = PlacementEngine().place(req, policy=policy,
                                  rng=np.random.default_rng(0))
    with backend.use("jax") as be:
        before = be.stats["numpy_fallbacks"]
        out = PlacementEngine().place(req, policy=policy,
                                      rng=np.random.default_rng(0))
        assert be.stats["numpy_fallbacks"] == before    # served by jax
    assert np.array_equal(out.placement, ref.placement), \
        f"{host_name} faulty={faulty} {policy}"
    assert out.placement.dtype.kind == "i"          # integer-exact
    assert ref.placement.dtype.kind == "i"
    np.testing.assert_allclose(out.hop_bytes, ref.hop_bytes, rtol=RTOL)


@pytest.mark.parametrize("case", ["asymmetric-guest", "faulty-lazy-torus"])
def test_numpy_fallbacks_counted(case):
    """Calls the jitted kernels cannot serve run the NumPy kernels, and
    each one is counted: a guest outside the symmetric CommGraph
    convention, and a faulty torus with a straggler above the lazy
    threshold (stragglers price links by slowdown, so its lazy distance
    has no implicit spec)."""
    from repro.core import mapping_jax

    topo = TorusTopology((4, 4, 4))
    with backend.use("jax") as be:
        before = be.stats["numpy_fallbacks"]
        if case == "asymmetric-guest":
            G = np.triu(npb_dt_like(24).comm.G_v)
            P = np.stack([np.random.default_rng(s).permutation(64)[:24]
                          for s in range(2)])
            out = mapping.hop_bytes_batch(G, topo.hop_matrix(), P)
            with backend.use("numpy"):
                ref = mapping.hop_bytes_batch(G, topo.hop_matrix(), P)
            np.testing.assert_array_equal(out, ref)
        else:
            faulty = _request(topo, 24, faulty=True)
            req = PlacementRequest(comm=faulty.comm, topology=topo,
                                   p_f=faulty.route_p_f(),
                                   straggler=np.full(topo.n_nodes, 0.5))
            engine = PlacementEngine(lazy_threshold=32)
            assert not mapping_jax.lazy_supported(
                engine._weights_for(topo, req, req.route_p_f()))
            plan = engine.place(req, policy="tofa",
                                rng=np.random.default_rng(0))
            assert len(set(plan.placement.tolist())) == 24
        assert be.stats["numpy_fallbacks"] > before


def test_fractional_weight_guest_quality():
    """lammps_like carries non-dyadic all-reduce weights: cross-backend
    placements may legally differ (BLAS vs XLA reduction order), but the
    jax backend must stay within quality tolerance of numpy."""
    topo = TorusTopology((4, 4, 4))
    wl = lammps_like(48)
    req = PlacementRequest(comm=wl.comm, topology=topo)
    ref = PlacementEngine().place(req, policy="tofa",
                                  rng=np.random.default_rng(0))
    with backend.use("jax"):
        out = PlacementEngine().place(req, policy="tofa",
                                      rng=np.random.default_rng(0))
    assert out.hop_bytes <= ref.hop_bytes * 1.05
    assert len(set(out.placement.tolist())) == wl.n_ranks


def test_engine_backend_kwarg():
    """PlacementEngine(backend='jax') pins the backend per engine."""
    topo = TorusTopology((4, 4, 4))
    req = _request(topo, 24, faulty=True)
    ref = PlacementEngine().place(req, rng=np.random.default_rng(0))
    out = PlacementEngine(backend="jax").place(req,
                                               rng=np.random.default_rng(0))
    assert np.array_equal(out.placement, ref.placement)
    assert backend.active().name == "numpy"      # scope did not leak


# ------------------------------------------------------------ place_many
@pytest.mark.parametrize("be", ["numpy", "jax"])
def test_place_many_equals_sequential(be):
    topo = TorusTopology((4, 4, 4))
    requests = [_request(topo, n, faulty) for n, faulty in
                [(12, False), (24, True), (18, False), (12, True)]]
    with backend.use(be):
        engine = PlacementEngine()
        seq = [engine.place(r, policy="tofa") for r in requests]
        batch = PlacementEngine().place_many(requests, policy="tofa")
    for s, b in zip(seq, batch):
        assert np.array_equal(s.placement, b.placement)
        assert s.hop_bytes == b.hop_bytes


def test_place_many_exclusive_disjoint():
    topo = TorusTopology((4, 4, 4))
    requests = [_request(topo, 20, False) for _ in range(3)]
    plans = PlacementEngine().place_many(requests, policy="tofa",
                                         exclusive=True)
    used: set[int] = set()
    for p in plans:
        ids = set(int(x) for x in p.placement)
        assert not (ids & used)          # exclusive node allocation
        used |= ids
    with pytest.raises(ValueError):
        PlacementEngine().place_many(
            [_request(topo, 24, False) for _ in range(3)],
            policy="tofa", exclusive=True)   # 72 procs > 64 nodes


def test_place_many_per_request_policies():
    topo = TorusTopology((4, 4, 4))
    requests = [_request(topo, 12, False), _request(topo, 12, False)]
    plans = PlacementEngine().place_many(requests,
                                         policy=["linear", "tofa"])
    assert plans[0].policy == "linear" and plans[1].policy == "tofa"
    with pytest.raises(ValueError):
        PlacementEngine().place_many(requests, policy=["tofa"])


# ------------------------------------------------------------ dtype policy
def test_float32_mode_runs_and_returns_int_placements():
    topo = TorusTopology((4, 4, 4))
    req = _request(topo, 24, faulty=True)
    with backend.use("jax", dtype="float32"):
        assert backend.active().dtype == "float32"
        plan = PlacementEngine().place(req, policy="tofa",
                                       rng=np.random.default_rng(0))
    assert plan.placement.dtype.kind == "i"
    assert len(set(plan.placement.tolist())) == 24
    # float32 quality stays in the same ballpark as the exact float64 run
    ref = PlacementEngine().place(req, policy="tofa",
                                  rng=np.random.default_rng(0))
    assert plan.hop_bytes <= ref.hop_bytes * 1.10


def test_numpy_default_untouched():
    """Importing/using the jax backend must not change the default path."""
    assert backend.active().name == "numpy"
    topo = TorusTopology((4, 4, 4))
    wl = halo3d((2, 3, 4))
    req = PlacementRequest(comm=wl.comm, topology=topo)
    a = PlacementEngine().place(req, rng=np.random.default_rng(0))
    with backend.use("jax"):
        pass
    b = PlacementEngine().place(req, rng=np.random.default_rng(0))
    assert np.array_equal(a.placement, b.placement)


def test_backend_registry_errors():
    with pytest.raises(ValueError):
        backend.get_backend("tensorflow")
    with pytest.raises(ValueError):
        backend.get_backend("jax", dtype="float16")


def test_reference_impl_wins_over_jax_backend():
    """use_reference_impl must run the scalar loops even when the jax
    backend is active — the reference baseline is backend-independent."""
    topo = TorusTopology((4, 4, 4))
    wl = npb_dt_like(24)
    D = topo.hop_matrix()
    P = np.stack([np.random.default_rng(s).permutation(topo.n_nodes)[:24]
                  for s in range(2)])
    with mapping.use_reference_impl():
        ref = mapping.refine_batch(wl.comm.G_v, D, P)
        with backend.use("jax"):
            out = mapping.refine_batch(wl.comm.G_v, D, P)
            assert mapping.greedy_placement is \
                mapping.greedy_placement_reference
    assert np.array_equal(out, ref)


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to ``.jax_cache/`` at the repository root — a fixed path,
    since the directory is part of the cache key.  Only on TPU is every
    compile cached, however quick."""
    import types
    from pathlib import Path

    class Config:
        jax_compilation_cache_dir = None

        def update(self, name, value):
            setattr(self, name, value)

    platform = ["cpu"]
    fake = types.SimpleNamespace(config=Config(),
                                 default_backend=lambda: platform[0])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    backend._init_compile_cache(fake)
    assert fake.config.jax_compilation_cache_dir is None
    assert not hasattr(fake.config,
                       "jax_persistent_cache_min_compile_time_secs")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    backend._init_compile_cache(fake)
    root = Path(__file__).resolve().parents[1]
    assert fake.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    platform[0] = "tpu"
    backend._init_compile_cache(fake)
    assert fake.config.jax_persistent_cache_min_compile_time_secs == 0
