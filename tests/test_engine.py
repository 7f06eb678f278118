"""PlacementEngine API: registry, request validation, caching, replace,
topology protocol, and shim equivalence."""
import numpy as np
import pytest

from repro.core.engine import (PlacementEngine, PlacementRequest, Topology,
                               default_engine)
from repro.core.fattree import FatTreeTopology
from repro.core.placement import Fabric
from repro.core.policies import (DuplicatePolicyError, PolicyOutput,
                                 UnknownPolicyError, available_policies,
                                 get_policy, register_policy,
                                 unregister_policy)
from repro.core.tofa import POLICIES, place
from repro.core.topology import TorusTopology
from repro.workloads.patterns import lammps_like, npb_dt_like


@pytest.fixture()
def engine():
    return PlacementEngine()


@pytest.fixture(scope="module")
def torus():
    return TorusTopology((4, 4, 4))


# ------------------------------------------------------------------ registry
def test_registry_contains_seed_policies():
    assert set(available_policies()) >= {"linear", "random", "greedy",
                                         "topo", "tofa"}
    assert POLICIES == available_policies()


def test_unknown_policy_raises(engine, torus):
    req = PlacementRequest(comm=lammps_like(8).comm, topology=torus)
    with pytest.raises(UnknownPolicyError):
        engine.place(req, policy="definitely-not-registered")
    # legacy callers catch ValueError
    with pytest.raises(ValueError):
        get_policy("definitely-not-registered")


def test_duplicate_registration_raises():
    with pytest.raises(DuplicatePolicyError):
        @register_policy("linear")
        class Dup:                                      # pragma: no cover
            fault_aware = False

            def place(self, ctx):
                return PolicyOutput(np.arange(ctx.n_procs))


def test_third_party_policy_registers_and_runs(engine, torus):
    @register_policy("test-reverse-linear")
    class ReverseLinear:
        fault_aware = False

        def place(self, ctx):
            return PolicyOutput(ctx.available[:ctx.n_procs][::-1].copy())

    try:
        req = PlacementRequest(comm=lammps_like(8).comm, topology=torus)
        plan = engine.place(req, policy="test-reverse-linear")
        assert list(plan.placement) == list(range(8))[::-1]
        assert plan.policy == "test-reverse-linear"
    finally:
        unregister_policy("test-reverse-linear")
    assert "test-reverse-linear" not in available_policies()


# ---------------------------------------------------------------- validation
def test_request_rejects_too_many_processes(torus):
    with pytest.raises(ValueError, match="processes"):
        PlacementRequest(comm=lammps_like(100).comm, topology=torus)


def test_request_rejects_insufficient_available(torus):
    with pytest.raises(ValueError, match="available"):
        PlacementRequest(comm=lammps_like(8).comm, topology=torus,
                         available=np.arange(4))


def test_request_rejects_bad_metric_and_shapes(torus):
    comm = lammps_like(8).comm
    with pytest.raises(ValueError, match="metric"):
        PlacementRequest(comm=comm, topology=torus, metric="latency")
    with pytest.raises(ValueError, match="p_f"):
        PlacementRequest(comm=comm, topology=torus, p_f=np.zeros(7))
    with pytest.raises(ValueError, match="range"):
        PlacementRequest(comm=comm, topology=torus,
                         available=np.arange(60, 70))


# ------------------------------------------------------------------- engine
def test_engine_runs_every_policy(engine, torus):
    req = PlacementRequest(comm=npb_dt_like(20).comm, topology=torus)
    for pol in ("linear", "random", "greedy", "topo", "tofa"):
        plan = engine.place(req, policy=pol, rng=np.random.default_rng(1))
        assert len(plan.placement) == 20
        assert len(set(plan.placement.tolist())) == 20, pol
        assert plan.policy == pol
        assert plan.wall_time_s >= 0
        assert plan.cost_breakdown()["hop_bytes"] == plan.hop_bytes


def test_weight_matrix_cache_hit(engine, torus):
    p_f = np.zeros(64)
    p_f[[3, 17]] = 0.1
    w1 = engine.weights(torus, p_f)
    w2 = engine.weights(torus, p_f.copy())
    assert w1 is w2
    assert engine.cache_stats()["weight_hits"] == 1
    # all-healthy degenerates to the cached hop matrix
    assert engine.weights(torus, np.zeros(64)) is engine.hops(torus)


def test_shim_equivalence_fixed_seed(engine, torus):
    """place() must return the same placement as the engine for all seed
    policies (the shim is a thin wrapper, not a fork)."""
    wl = npb_dt_like(20)
    p_f = np.zeros(64)
    p_f[np.random.default_rng(5).choice(64, 6, replace=False)] = 0.05
    req = PlacementRequest(comm=wl.comm, topology=torus, p_f=p_f)
    for pol in ("linear", "random", "greedy", "topo", "tofa"):
        legacy = place(pol, wl.comm, torus, p_f,
                       rng=np.random.default_rng(0))
        plan = engine.place(req, policy=pol, rng=np.random.default_rng(0))
        assert (legacy.placement == plan.placement).all(), pol
        assert legacy.hop_bytes == plan.hop_bytes


# ------------------------------------------------------------------ replace
def test_replace_avoids_failed_nodes(engine, torus):
    wl = npb_dt_like(20)
    req = PlacementRequest(comm=wl.comm, topology=torus)
    plan = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    failed = plan.placement[:3].tolist()
    new = engine.replace(plan, failed)
    assert new.provenance == "replace-incremental"
    assert not set(failed) & set(new.placement.tolist())
    assert len(set(new.placement.tolist())) == 20
    assert new.faulty_nodes_used == 0
    # unaffected processes did not move
    moved = np.flatnonzero(plan.placement != new.placement)
    assert set(moved.tolist()) == {0, 1, 2}
    # failed nodes are certain outages in the new request
    assert (new.request.p_f[failed] == 1.0).all()
    assert not np.isin(failed, new.request.available_ids).any()


def test_replace_full_fallback_when_mostly_displaced(engine, torus):
    wl = lammps_like(8)
    plan = engine.place(PlacementRequest(comm=wl.comm, topology=torus),
                        policy="linear")
    new = engine.replace(plan, plan.placement[:6])
    assert new.provenance == "replace-full"
    assert not np.isin(new.placement, plan.placement[:6]).any()


def test_replace_raises_without_capacity():
    t = TorusTopology((2, 2))
    plan = PlacementEngine().place(
        PlacementRequest(comm=lammps_like(4).comm, topology=t),
        policy="linear")
    with pytest.raises(ValueError, match="surviving"):
        PlacementEngine().replace(plan, [0])


# --------------------------------------------------------- topology protocol
def test_topology_protocol_instances(torus):
    for topo in (torus, Fabric(pod_dims=(4, 4), n_pods=2),
                 FatTreeTopology(4)):
        assert isinstance(topo, Topology)


def test_fat_tree_distances():
    ft = FatTreeTopology(4)
    assert ft.n_nodes == 16
    h = ft.hop_matrix()
    assert h[0, 0] == 0          # same host
    assert h[0, 1] == 2          # same edge switch
    assert h[0, 2] == 4          # same pod, different edge
    assert h[0, 4] == 6          # different pod
    assert (h == h.T).all()


def test_fat_tree_tofa_avoids_faulty_hosts():
    ft = FatTreeTopology(8)      # 128 hosts
    wl = npb_dt_like(24)
    p_f = np.zeros(ft.n_nodes)
    p_f[np.random.default_rng(2).choice(ft.n_nodes, 16, replace=False)] = 0.1
    eng = PlacementEngine()
    plan = eng.place(PlacementRequest(comm=wl.comm, topology=ft, p_f=p_f),
                     policy="tofa")
    assert plan.faulty_nodes_used == 0
    assert len(set(plan.placement.tolist())) == 24
    # fault-aware beats linear on the weighted metric under faults
    lin = eng.place(PlacementRequest(comm=wl.comm, topology=ft, p_f=p_f),
                    policy="linear")
    assert plan.hop_bytes_fault_weighted is not None
    assert lin.faulty_nodes_used > 0 or plan.hop_bytes <= lin.hop_bytes


def test_fabric_via_engine_matches_chip_count():
    fab = Fabric(pod_dims=(4, 4), n_pods=2)
    assert fab.n_nodes == fab.n_chips == 32
    eng = PlacementEngine()
    plan = eng.place(PlacementRequest(comm=lammps_like(8).comm, topology=fab),
                     policy="topo")
    assert len(set(plan.placement.tolist())) == 8


def test_default_engine_is_shared():
    assert default_engine() is default_engine()


def test_replace_rejects_out_of_range_node_ids(engine, torus):
    plan = engine.place(PlacementRequest(comm=lammps_like(8).comm,
                                         topology=torus), policy="linear")
    with pytest.raises(ValueError, match="range"):
        engine.replace(plan, [999])


def test_replace_honours_refreshed_availability(engine, torus):
    """The plan's request is a submit-time snapshot; a live scheduler passes
    current p_f/available so re-placement avoids nodes that went down or
    drained after submission, not just the newly failed ones."""
    wl = lammps_like(8)
    plan = engine.place(PlacementRequest(comm=wl.comm, topology=torus),
                        policy="linear")           # nodes 0..7
    died_earlier = [8, 9, 10]                       # down since submit
    now_avail = np.setdiff1d(np.arange(64), died_earlier)
    p_now = np.zeros(64)
    p_now[died_earlier] = 1.0
    new = engine.replace(plan, [int(plan.placement[0])],
                         p_f=p_now, available=now_avail)
    assert int(plan.placement[0]) not in new.placement
    assert not np.isin(new.placement, died_earlier).any()
    assert (new.request.p_f[died_earlier] == 1.0).all()


@pytest.mark.parametrize("lazy", [False, True])
@pytest.mark.parametrize("route_faulty", [True, False])
def test_tofa_fallback_stays_on_allocatable_nodes(route_faulty, lazy):
    """Fewer healthy free nodes than ranks: tofa falls back to selecting
    from the whole fault-weighted topology, and must still never pick a
    node another job holds — busy or fault flavored, dense or lazy."""
    from repro.core.state import ClusterState
    topo = TorusTopology((4, 4, 4))
    p_f = np.zeros(64)
    p_f[1::2] = 0.3
    busy = np.arange(40)                # 24 free: 12 healthy, 12 faulty
    view = ClusterState.healthy(64).with_outage(p_f).overlay(
        busy, route_faulty=route_faulty)
    req = PlacementRequest(comm=npb_dt_like(18).comm, topology=topo,
                           state=view)
    engine = PlacementEngine(lazy_threshold=32 if lazy else None)
    plan = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    assert len(set(plan.placement.tolist())) == 18
    assert np.isin(plan.placement, view.available_ids()).all()


def test_tofa_lazy_last_resort_stays_on_allocatable_nodes():
    """With no hierarchy to grow a ball from, the lazy candidate search
    falls back to frontier growth over the whole metric; that growth too
    must only take allocatable nodes."""
    from repro.core.policies import PolicyContext
    from repro.core.policies.tofa import TofaMultilevelPolicy
    topo = TorusTopology((4, 4, 4))
    available = np.arange(0, 64, 3)     # 22 free nodes, none consecutive
    p_f = np.ones(64)
    p_f[available] = 0.0
    W = topo.lazy_distance(p_f)
    ctx = PolicyContext(request=None, G_w=npb_dt_like(18).comm.weights(),
                        coords=topo.coords_array(), hops=None, p_f=p_f,
                        available=available, rng=np.random.default_rng(0),
                        _weights=W)
    used_window, candidates = TofaMultilevelPolicy._candidates_lazy(ctx)
    assert not used_window and len(candidates) == 1
    assert len(candidates[0]) == 18
    assert np.isin(candidates[0], available).all()
