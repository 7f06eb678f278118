"""ClusterState: lifecycle, overlay/diff algebra, epoch-keyed engine
caching (zero misses on no-op heartbeat rounds), delta weight refreshes,
replace fast-path, and cross-backend parity across a state churn
sequence."""
import numpy as np
import pytest

from repro.cluster.nodes import NodeState
from repro.cluster.scheduler import Job, Scheduler
from repro.core.backend import has_jax
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.fattree import FatTreeTopology
from repro.core.state import ClusterState, NodeHealth
from repro.core.topology import TorusTopology
from repro.workloads.patterns import lammps_like, npb_dt_like

from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()


# ------------------------------------------------------------- lifecycle
def test_healthy_state_has_all_nodes_allocatable():
    s = ClusterState.healthy(16)
    assert s.n_nodes == 16
    assert (s.available_ids() == np.arange(16)).all()
    assert (s.outage_vector() == 0).all()
    assert s.snapshot() is s
    assert s.health_of(3) == NodeHealth.UP


def test_lifecycle_transitions_mint_monotonic_epochs():
    s0 = ClusterState.healthy(8)
    s1 = s0.with_health([2], NodeHealth.DEGRADED)
    s2 = s1.with_health([2], NodeHealth.DRAINED)
    s3 = s2.with_health([2], NodeHealth.DOWN)
    s4 = s3.with_health([2], NodeHealth.UP)
    epochs = [s.epoch for s in (s0, s1, s2, s3, s4)]
    assert epochs == sorted(epochs) and len(set(epochs)) == 5
    # DEGRADED stays allocatable; DRAINED and DOWN do not
    assert 2 in s1.available_ids()
    assert 2 not in s2.available_ids()
    assert 2 not in s3.available_ids()
    assert 2 in s4.available_ids()
    # non-allocatable nodes are pinned to certain outage
    assert s2.outage_vector()[2] == 1.0 and s1.outage_vector()[2] == 0.0


def test_noop_transition_returns_same_state():
    s0 = ClusterState.healthy(8)
    assert s0.with_health([3], NodeHealth.UP) is s0
    assert s0.with_outage(np.zeros(8)) is s0
    assert s0.overlay(unavailable=[]) is s0


def test_with_outage_atol_and_pattern():
    s0 = ClusterState.healthy(8).with_outage(np.full(8, 0.2))
    # drift within atol: same state, same epoch
    assert s0.with_outage(np.full(8, 0.25), atol=0.1) is s0
    # drift beyond atol mints
    s1 = s0.with_outage(np.full(8, 0.5), atol=0.1)
    assert s1 is not s0 and s1.epoch > s0.epoch
    # a p_f > 0 pattern flip always mints, regardless of atol
    p = np.full(8, 0.2)
    p[3] = 0.0
    s2 = s0.with_outage(p, atol=None)
    assert s2 is not s0
    # pattern-only mode (atol=None) ignores pure magnitude drift
    assert s0.with_outage(np.full(8, 0.9), atol=None) is s0


def test_states_are_immutable():
    s = ClusterState.healthy(4)
    with pytest.raises(ValueError):
        s.health[0] = 3
    with pytest.raises(ValueError):
        s.p_f[0] = 0.5


def test_from_arrays_interns_by_content():
    p = np.zeros(16)
    p[5] = 0.1
    a = ClusterState.from_arrays(16, p_f=p)
    b = ClusterState.from_arrays(16, p_f=p.copy())
    assert a is b
    c = ClusterState.from_arrays(16, p_f=p, available=np.arange(8))
    assert c is not a
    assert (c.available_ids() == np.arange(8)).all()
    assert c.outage_vector()[12] == 1.0   # outside available == DOWN


def test_groups_carried_and_queryable():
    s = ClusterState.healthy(8, groups=[[0, 1, 2, 3], [4, 5, 6, 7]])
    assert s.group_of(5) == 1 and s.group_of(0) == 0
    s2 = s.with_health([1], NodeHealth.DOWN)
    assert s2.groups == s.groups


def test_from_arrays_interning_keys_on_groups():
    ungrouped = ClusterState.from_arrays(8)
    grouped = ClusterState.from_arrays(8, groups=[[0, 1], [2, 3]])
    assert grouped is not ungrouped
    assert grouped.group_of(1) == 0 and ungrouped.group_of(1) is None
    assert ClusterState.from_arrays(8, groups=[[0, 1], [2, 3]]) is grouped


# --------------------------------------------------------- overlay / diff
def test_overlay_masks_without_minting_epoch():
    s = ClusterState.healthy(16)
    o = s.overlay(unavailable=[3, 4])
    assert o.epoch == s.epoch and o.key != s.key
    assert 3 not in o.available_ids() and 3 in s.available_ids()
    assert o.outage_vector()[3] == 1.0
    # same masked set => same key (cache-stable)
    assert s.overlay(unavailable=[4, 3]).key == o.key
    # composing overlays unions the masks against the same base
    oo = o.overlay(unavailable=[7])
    assert set(np.setdiff1d(s.available_ids(), oo.available_ids())) \
        == {3, 4, 7}
    assert oo.key == s.overlay(unavailable=[3, 4, 7]).key


def test_overlay_cannot_evolve():
    o = ClusterState.healthy(8).overlay(unavailable=[1])
    with pytest.raises(ValueError):
        o.with_health([2], NodeHealth.DOWN)


def test_diff_identifies_changed_nodes():
    s0 = ClusterState.healthy(16)
    s1 = s0.with_health([2, 9], NodeHealth.DOWN)
    d = s0.diff(s1)
    assert set(d.nodes.tolist()) == {2, 9}
    assert set(d.lost().tolist()) == {2, 9}
    assert d.touches(np.array([1, 2, 3])) and not d.touches(np.array([4, 5]))
    # symmetric membership; lost() is directional
    assert (s1.diff(s0).nodes == d.nodes).all()
    assert len(s1.diff(s0).lost()) == 0
    # self-diff is empty
    assert not s0.diff(s0)


def test_diff_sees_overlay_masking():
    s = ClusterState.healthy(8)
    o = s.overlay(unavailable=[5])
    assert set(s.diff(o).nodes.tolist()) == {5}
    assert set(s.diff(o).lost().tolist()) == {5}


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=2, max_value=32), st.data())
def test_overlay_diff_algebra_properties(n, data):
    """Property: overlay availability is base minus mask; diff is exactly
    the symmetric difference of effective health; overlay keys are a
    function of (base, masked set)."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    p = np.where(rng.random(n) < 0.3, rng.random(n), 0.0)
    s = ClusterState.healthy(n).with_outage(p)
    k = int(rng.integers(0, n))
    masked = rng.choice(n, size=k, replace=False)
    o = s.overlay(unavailable=masked)
    expect = np.setdiff1d(np.arange(n), masked)
    assert (o.available_ids() == expect).all()
    # diff(s, o) == masked set exactly (p_f pinning tracks allocatability)
    assert set(s.diff(o).nodes.tolist()) == set(int(x) for x in masked)
    # key determinism: rebuilding the same overlay reproduces the key
    assert s.overlay(unavailable=np.sort(masked)).key == o.key \
        or k == 0
    # epochs never move backwards
    s2 = s.with_health(masked, NodeHealth.DOWN) if k else s
    assert s2.epoch >= s.epoch


# ------------------------------------------- engine epoch-keyed caching
def test_request_from_state_exposes_legacy_views():
    topo = TorusTopology((4, 4))
    s = ClusterState.healthy(16).with_health([3], NodeHealth.DOWN)
    req = PlacementRequest(comm=lammps_like(8).comm, topology=topo, state=s)
    assert 3 not in req.available_ids
    assert req.p_f[3] == 1.0
    assert req.effective_p_f()[3] == 1.0
    with pytest.raises(ValueError, match="not both"):
        PlacementRequest(comm=lammps_like(8).comm, topology=topo, state=s,
                         p_f=np.zeros(16))


def test_same_epoch_hits_weight_and_memo_caches():
    topo = TorusTopology((4, 4, 4))
    engine = PlacementEngine()
    s = ClusterState.healthy(64).with_outage(
        np.where(np.arange(64) < 6, 0.1, 0.0))
    wl = npb_dt_like(20)
    req = PlacementRequest(comm=wl.comm, topology=topo, state=s)
    p1 = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
    misses = engine.cache_stats()["weight_misses"]
    req2 = PlacementRequest(comm=wl.comm, topology=topo, state=s)
    p2 = engine.place(req2, policy="tofa", rng=np.random.default_rng(0))
    stats = engine.cache_stats()
    assert stats["weight_misses"] == misses      # zero new derivations
    assert stats["weight_hits"] >= 1 and stats["shared_hits"] >= 1
    assert (p1.placement == p2.placement).all()


def test_heartbeat_round_with_unchanged_health_zero_cache_misses():
    """Regression for the deleted quantized-estimated_outage hack: a
    heartbeat round that does not change health must not mint an epoch,
    so a following placement hits every engine cache."""
    topo = TorusTopology((4, 4, 4))
    sch = Scheduler(topo)
    truth = np.zeros(64)
    truth[:5] = 0.3
    sch.registry.set_outage_probabilities(range(5), 0.3)
    sch.monitor.simulate_rounds(np.random.default_rng(7), truth, 400)
    rec_a = sch.submit(Job(npb_dt_like(12), distribution="tofa"))
    rec_b = sch.submit(Job(npb_dt_like(12), distribution="tofa"))
    assert rec_a.state == rec_b.state == "running"
    sch.complete(rec_b.job.job_id)
    epoch0 = sch.cluster_state().epoch
    before = dict(sch.engine.cache_stats())
    # jittery but health-preserving heartbeat rounds (estimates drift
    # inside p_f_atol, no lifecycle transitions), then a placement
    # against the identical busy profile rec_b saw
    for _ in range(5):
        sch.heartbeat_round(np.ones(64, dtype=bool))
    assert sch.cluster_state().epoch == epoch0
    rec_c = sch.submit(Job(npb_dt_like(12), distribution="tofa"))
    assert rec_c.state == "running"
    after = sch.engine.cache_stats()
    assert after["weight_misses"] == before["weight_misses"]
    assert after["shared_misses"] == before["shared_misses"]
    assert after["hop_misses"] == before["hop_misses"]


def test_estimator_jitter_would_have_missed_on_byte_keys():
    """The jitter really is there — raw byte keys would change: the
    monitor's estimates move between rounds even though health did not."""
    topo = TorusTopology((4, 4))
    sch = Scheduler(topo)
    truth = np.zeros(16)
    truth[0] = 0.3
    sch.registry.set_outage_probabilities([0], 0.3)
    rng = np.random.default_rng(3)
    sch.monitor.simulate_rounds(rng, truth, 150)
    e0 = sch.monitor.outage_probabilities()
    s0 = sch.cluster_state()
    replies = np.ones(16, dtype=bool)
    replies[0] = False                      # missed beats: estimate moves
    jittered = False
    for _ in range(6):
        sch.heartbeat_round(replies)
        jittered |= e0.tobytes() != sch.monitor.outage_probabilities() \
            .tobytes()
    assert jittered                         # byte key would have missed
    assert sch.cluster_state() is s0        # epoch key does not


# ------------------------------------------------ delta weight refreshes
def test_fattree_delta_weight_update_bit_identical():
    ft = FatTreeTopology(4)
    p0 = np.zeros(16)
    p0[[1, 2]] = 0.3
    W0 = ft.weight_matrix(p0)
    p1 = np.zeros(16)
    p1[[2, 9]] = 0.1
    changed = np.flatnonzero((p0 > 0) != (p1 > 0))
    assert (ft.weight_matrix_update(W0, changed, p1)
            == ft.weight_matrix(p1)).all()


def test_engine_uses_delta_updates_across_churn():
    topo = FatTreeTopology(4)
    engine = PlacementEngine()
    wl = npb_dt_like(8)
    s = ClusterState.healthy(16).with_outage(
        np.where(np.arange(16) < 2, 0.2, 0.0))
    rng = np.random.default_rng(0)
    full = PlacementEngine()                 # reference: fresh engine per state
    for step in range(4):
        req = PlacementRequest(comm=wl.comm, topology=topo, state=s)
        plan = engine.place(req, policy="tofa",
                            rng=np.random.default_rng(step))
        ref = full.place(PlacementRequest(comm=wl.comm, topology=topo,
                                          state=s),
                         policy="tofa", rng=np.random.default_rng(step))
        assert (plan.placement == ref.placement).all()
        assert plan.hop_bytes == ref.hop_bytes
        s = s.with_health([int(rng.integers(0, 16))], NodeHealth.DOWN)
    assert engine.cache_stats()["weight_delta_updates"] >= 2


def test_engine_derives_torus_weights_in_full_across_churn():
    # the torus has no delta path: every change of its penalty flags or
    # slowdowns is one full, vectorised derivation, equal to what a fresh
    # engine serves; a change of p_f alone reuses the last matrix
    topo = TorusTopology((4, 4, 4))
    engine = PlacementEngine()
    s = ClusterState.healthy(64).with_outage(
        np.where(np.arange(64) < 4, 0.2, 0.0))
    rng = np.random.default_rng(0)
    for _ in range(4):
        p_f = s.outage_vector()
        slow = np.zeros(64)
        slow[rng.integers(0, 64)] = 0.5
        W = engine.weights(topo, p_f, straggler=slow)
        assert (W == PlacementEngine().weights(topo, p_f,
                                               straggler=slow)).all()
        assert (W == topo.weight_matrix(p_f, straggler=slow)).all()
        s = s.with_health([int(rng.integers(0, 64))], NodeHealth.DOWN)
    assert engine.weights(topo, np.where(p_f > 0, 1.0, 0.0),
                          straggler=slow) is W
    stats = engine.cache_stats()
    assert stats["weight_misses"] == 5
    assert stats["weight_delta_updates"] == 0


# --------------------------------------------------- replace fast-path
def test_replace_skips_when_diff_misses_placement():
    topo = TorusTopology((4, 4, 4))
    engine = PlacementEngine()
    wl = npb_dt_like(8)
    plan = engine.place(
        PlacementRequest(comm=wl.comm, topology=topo,
                         state=ClusterState.healthy(64)),
        policy="linear")
    unused = [int(x) for x in
              np.setdiff1d(np.arange(64), plan.placement)[:3]]
    out = engine.replace(plan, unused)
    assert out is plan                       # zero-work fast path
    assert engine.cache_stats()["replace_skips"] == 1
    # diff-driven form: new state lost only unused nodes -> same skip
    s2 = plan.request.state.with_health(unused, NodeHealth.DOWN)
    out2 = engine.replace(plan, state=s2)
    assert out2 is plan
    # but a diff touching the placement does re-place
    victim = int(plan.placement[0])
    s3 = plan.request.state.with_health([victim], NodeHealth.DOWN)
    out3 = engine.replace(plan, state=s3)
    assert out3 is not plan
    assert victim not in out3.placement
    assert out3.provenance == "replace-incremental"


def test_replace_diff_driven_matches_failed_nodes_form():
    topo = TorusTopology((4, 4, 4))
    engine = PlacementEngine()
    wl = npb_dt_like(10)
    base = ClusterState.healthy(64)
    plan = engine.place(PlacementRequest(comm=wl.comm, topology=topo,
                                         state=base), policy="tofa")
    victims = [int(plan.placement[0]), int(plan.placement[3])]
    by_nodes = engine.replace(plan, victims,
                              rng=np.random.default_rng(1))
    new_state = base.with_health(victims, NodeHealth.DOWN)
    by_diff = engine.replace(plan, state=new_state,
                             rng=np.random.default_rng(1))
    assert (by_nodes.placement == by_diff.placement).all()


# ------------------------------------------------ legacy-shim ordering
def test_replace_preserves_explicit_available_order():
    """The shim's equivalence promise: a plan placed over an explicitly
    *ordered* availability array must keep that order through replace
    (``linear`` consumes it sequentially)."""
    topo = TorusTopology((4, 4))
    engine = PlacementEngine()
    order = np.arange(15, 7, -1)            # 15, 14, ..., 8
    plan = engine.place(
        PlacementRequest(comm=lammps_like(4).comm, topology=topo,
                         available=order),
        policy="linear")
    assert plan.placement.tolist() == [15, 14, 13, 12]
    new = engine.replace(plan, [15], full=True)
    assert new.placement.tolist() == [14, 13, 12, 11]
    # and with the availability refreshed via the legacy kwarg
    new2 = engine.replace(plan, [15], full=True,
                          available=np.arange(15, 5, -1))
    assert new2.placement.tolist() == [14, 13, 12, 11]


def test_scheduler_placement_request_honours_custom_available():
    """An explicit what-if availability — custom order, possibly naming
    drained nodes — passes through verbatim instead of being re-sorted
    or silently filtered by the overlay."""
    topo = TorusTopology((4, 4))
    sch = Scheduler(topo)
    sch.registry.mark([9], NodeState.DRAINED)
    req = sch.placement_request(Job(lammps_like(3), distribution="linear"),
                                available=np.array([9, 3, 5]))
    assert req.available_ids.tolist() == [9, 3, 5]
    assert req.p_f[9] == 1.0                # belief still pins drained
    plan = sch.engine.place(req, policy="linear")
    assert plan.placement.tolist() == [9, 3, 5]
    # the id-ordered free subset still rides the epoch-keyed overlay
    req2 = sch.placement_request(Job(lammps_like(3)))
    assert req2.state.is_overlay or req2.state is sch.cluster_state()


# -------------------------------------------------- backend parity churn
@pytest.mark.skipif(not has_jax(), reason="jax not installed")
def test_backend_epoch_caches_bit_identical_across_churn():
    """numpy and jax engines must return bit-identical placements through
    a state churn sequence, and the jax device cache must transfer each
    epoch's matrix once."""
    from repro.core import backend as B
    topo = TorusTopology((4, 4, 4))
    wl = npb_dt_like(16)
    churn = [ClusterState.healthy(64).with_outage(
        np.where(np.arange(64) < 5, 0.1, 0.0))]
    for ids in ([7], [9, 33], [12]):
        churn.append(churn[-1].with_health(ids, NodeHealth.DOWN))
    eng_np = PlacementEngine(backend="numpy")
    eng_jx = PlacementEngine(backend="jax")
    jx = B.get_backend("jax")
    for s in churn:
        req = PlacementRequest(comm=wl.comm, topology=topo, state=s)
        a = eng_np.place(req, policy="tofa", rng=np.random.default_rng(0))
        b = eng_jx.place(PlacementRequest(comm=wl.comm, topology=topo,
                                          state=s),
                         policy="tofa", rng=np.random.default_rng(0))
        assert (a.placement == b.placement).all()
    # warm re-placement against the last epoch: no new device transfers
    transfers = jx.stats["transfers"]
    req = PlacementRequest(comm=wl.comm, topology=topo, state=churn[-1])
    eng_jx.place(req, policy="tofa", rng=np.random.default_rng(1))
    assert jx.stats["transfers"] == transfers
