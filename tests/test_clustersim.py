import numpy as np
import pytest

from repro.cluster.failures import (BernoulliPerJob, ExponentialLifetimes,
                                    FailureProcess, NoFailures, NodeEvent)
from repro.cluster.scheduler import Job, Scheduler
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.topology import TorusTopology
from repro.sim.batchsim import run_batch, run_scenario
from repro.sim.clustersim import ClusterSim, SimConfig
from repro.sim.network import TorusNetwork
from repro.sim.scenarios import run_preset
from repro.workloads.arrivals import burst_stream, serial_stream
from repro.workloads.patterns import halo3d, npb_dt_like


@pytest.fixture(scope="module")
def cluster():
    topo = TorusTopology((4, 4, 4))
    return topo, TorusNetwork(topo)


def _sched(cluster, **kw):
    topo, net = cluster
    return Scheduler(topo, net=net, **kw)


# ----------------------------------------------------- paper equivalence
def _event_sim_batch(topo, net, wl, pol, fm, known, n_instances, seed,
                     engine, **cfg):
    """Mirror run_batch through the event loop: same engine, same RNG."""
    rng = np.random.default_rng(seed)
    plan = engine.place(
        PlacementRequest(comm=wl.comm, topology=topo, p_f=known),
        policy=pol, rng=rng)
    sim = ClusterSim(
        Scheduler(topo, net=net, engine=engine),
        serial_stream([wl] * n_instances, policy=pol,
                      fixed_placement=plan.placement),
        attempt_failures=fm, rng=rng, config=SimConfig(**cfg))
    return sim.run()


def test_event_sim_matches_run_batch_exactly(cluster):
    """Serial arrivals + per-batch Bernoulli N_f: the event simulator
    reproduces run_batch completion times bit-for-bit (same RNG order)."""
    topo, net = cluster
    wl = npb_dt_like(24)
    cand = np.random.default_rng(5).choice(64, 8, replace=False)
    fm = BernoulliPerJob(cand, 0.05)
    known = fm.outage_vector(64)
    engine = PlacementEngine()
    for pol in ("linear", "tofa"):
        rb = run_batch(wl, pol, net, fm, known, n_instances=40,
                       rng=np.random.default_rng(11), engine=engine)
        res = _event_sim_batch(topo, net, wl, pol, fm, known, 40, 11,
                               engine)
        assert res.makespan == rb.completion_time
        assert res.aborted_attempts == rb.n_aborted_attempts
        assert not res.truncated


def test_event_sim_matches_run_batch_with_checkpointing(cluster):
    topo, net = cluster
    wl = npb_dt_like(24)
    fm = BernoulliPerJob(np.arange(16), 0.3)
    engine = PlacementEngine()
    rb = run_batch(wl, "linear", net, fm, None, n_instances=30,
                   rng=np.random.default_rng(2), engine=engine,
                   checkpoint_interval=0.02, checkpoint_overhead=0.001)
    res = _event_sim_batch(topo, net, wl, "linear", fm, None, 30, 2,
                           engine, checkpoint_interval=0.02,
                           checkpoint_overhead=0.001)
    # same draws and the same charge terms; only the floating-point
    # summation order differs (absolute event times vs one accumulator)
    assert res.makespan == pytest.approx(rb.completion_time, rel=1e-9)
    assert res.aborted_attempts == rb.n_aborted_attempts


def test_paper_preset_matches_run_scenario():
    """Acceptance: the Fig. 4/5 preset matches run_scenario per policy
    (criterion is 1%; the implementation is draw-for-draw identical)."""
    ev = run_preset("paper-fig4-5", fast=True, seed=3)
    ref = run_scenario(lambda: npb_dt_like(24), ("linear", "tofa"),
                       dims=(4, 4, 4), n_batches=2, n_instances=20,
                       n_faulty=8, p_f=0.02, seed=3)
    for pol in ("linear", "tofa"):
        a = ev["policies"][pol]["mean_completion"]
        b = ref[pol].mean_completion
        assert a == pytest.approx(b, rel=0.01)
        assert a == b, "draw-for-draw mirror should be exact, not just close"


# ------------------------------------------------- queueing and backfill
def test_queue_serialises_over_capacity(cluster):
    """Burst of jobs wider than half the cluster: they must run one at a
    time; completions drain the queue in FIFO order."""
    sch = _sched(cluster)
    wl = halo3d((2, 2, 2))            # 8 ranks
    jobs = burst_stream([halo3d((4, 4, 3)) for _ in range(3)],  # 48 ranks
                        policy="linear")
    sim = ClusterSim(sch, jobs, attempt_failures=NoFailures(),
                     rng=np.random.default_rng(0))
    res = sim.run()
    starts = sorted(j.first_start for j in res.jobs)
    # with 64 nodes and 48-rank jobs, starts must be strictly staggered
    assert starts[0] == 0.0 and starts[1] > 0.0 and starts[2] > starts[1]
    assert res.makespan == pytest.approx(sum(j.finish_time - j.first_start
                                             for j in res.jobs), rel=1e-6)


def test_backfill_lets_small_job_skip_blocked_head():
    topo = TorusTopology((4, 4))
    sch = Scheduler(topo)
    wide = Job(halo3d((4, 2, 2)), distribution="linear")    # 16 ranks
    wide2 = Job(halo3d((4, 2, 2)), distribution="linear")   # blocks
    small = Job(halo3d((2, 2, 2)), distribution="linear")   # 8 ranks
    assert sch.submit(wide).state == "running"
    assert sch.submit(wide2).state == "pending"   # head of queue, blocked
    rec_small = sch.submit(small)
    assert rec_small.state == "pending", "no free capacity at all"
    sch.complete(wide.job_id)
    # wide2 takes the whole machine again; small must wait behind it
    assert sch.records[wide2.job_id].state == "running"
    assert rec_small.state == "pending"
    sch.complete(wide2.job_id)
    assert rec_small.state == "running"


def test_backfill_disabled_is_strict_fifo():
    topo = TorusTopology((4, 4))
    for backfill, expected in ((True, "running"), (False, "pending")):
        sch = Scheduler(topo, backfill=backfill)
        sch.submit(Job(halo3d((3, 2, 2)), distribution="linear"))  # 12 ranks
        blocked = sch.submit(Job(halo3d((2, 2, 2)),
                                 distribution="linear"))           # 8 > 4
        assert blocked.state == "pending"
        small = sch.submit(Job(halo3d((2, 2, 1)), distribution="linear"))
        assert small.state == expected


# --------------------------------------- checkpoint / restart accounting
def test_mid_attempt_failure_restarts_from_checkpoint(cluster):
    """Time-based failure mid-attempt: work since the last checkpoint is
    lost, earlier work is preserved, and the job still finishes."""
    topo, net = cluster
    sch = _sched(cluster)
    wl = halo3d((2, 2, 2))
    t_ok = None
    # no-failure reference
    ref = ClusterSim(_sched(cluster), burst_stream([wl], policy="linear"),
                     rng=np.random.default_rng(0)).run()
    t_ok = ref.makespan
    ci = t_ok / 10
    victim_proc = ExponentialLifetimes([0], mtbf=t_ok * 0.6, mttr=0.01)
    sim = ClusterSim(
        sch, burst_stream([wl], policy="linear"),
        failure_process=victim_proc,
        config=SimConfig(checkpoint_interval=ci, checkpoint_overhead=0.0,
                         failure_horizon=t_ok * 0.9),
        rng=np.random.default_rng(1))
    res = sim.run()
    job = res.jobs[0]
    assert not res.truncated and job.finish_time > 0
    if job.aborts:
        # restarted: total elapsed exceeds t_ok, but by less than one full
        # re-run — the checkpoint preserved most of the aborted work
        # (bound includes the re-placed placement's runtime, within 2x)
        assert t_ok < res.makespan < 3 * t_ok
        assert job.attempts == job.aborts + 1


def test_node_failure_aborts_and_replaces(cluster):
    """A node death under a running job triggers engine.replace: the dead
    node leaves the placement and the job restarts."""
    topo, net = cluster
    sch = _sched(cluster)
    wl = halo3d((2, 2, 2))
    rec = sch.submit(Job(wl, distribution="linear"))
    victim = int(rec.placement.placement[0])
    affected = sch.handle_node_failure([victim])
    assert rec in affected and rec.state == "running"
    assert victim not in set(rec.placement.placement.tolist())
    assert rec.placement.provenance == "replace-incremental"
    assert rec.restarts == 1


def test_failure_requeues_job_when_survivors_cannot_hold_it():
    topo = TorusTopology((3, 3))
    sch = Scheduler(topo)
    rec = sch.submit(Job(halo3d((3, 3, 1)), distribution="linear"))
    assert rec.state == "running"
    victim = int(rec.placement.placement[0])
    affected = sch.handle_node_failure([victim])   # 8 survivors < 9 ranks
    assert rec in affected
    assert rec.state == "pending" and rec.placement is None
    assert rec.requeues == 1
    started = sch.recover([victim])
    assert rec in started and rec.state == "running"


class _FixedTrace(FailureProcess):
    """Deterministic trace for targeted failure timing in tests."""

    def __init__(self, events):
        self._events = list(events)

    def generate(self, rng, horizon):
        return [e for e in self._events if e.time < horizon]


def test_requeue_frees_capacity_for_pending_jobs():
    """A requeued job's released allocation must let other pending jobs
    start, even when no later SUBMIT/COMPLETE/RECOVER event arrives."""
    topo = TorusTopology((2, 4))                   # 8 nodes
    sch = Scheduler(topo)
    jobs = burst_stream([halo3d((3, 2, 1)),        # A: 6 ranks, runs first
                         halo3d((2, 2, 1))],       # B: 4 ranks, pending
                        policy="linear")
    # 4 of A's nodes die permanently: survivors (4) can't hold A, but
    # A's freed allocation gives B exactly the capacity it needs
    trace = _FixedTrace([NodeEvent(1e-4, "fail", (0, 1, 2, 3))])
    res = ClusterSim(sch, jobs, failure_process=trace,
                     config=SimConfig(failure_horizon=10.0),
                     rng=np.random.default_rng(0)).run()
    a, b = res.jobs
    assert a.finish_time < 0, "A cannot run on 4 surviving nodes"
    assert b.finish_time > 0, "B must start on the capacity A released"
    assert res.truncated, "run ends with A stuck pending"


def test_combined_mode_checkpoints_survive_node_failure(cluster):
    """attempt_failures + failure_process + checkpointing together: a
    node failure mid-attempt only loses work since the last checkpoint."""
    topo, net = cluster
    wl = halo3d((2, 2, 2))
    ref = ClusterSim(_sched(cluster), burst_stream([wl], policy="linear"),
                     rng=np.random.default_rng(0)).run()
    t_ok = ref.makespan
    trace = _FixedTrace([NodeEvent(0.55 * t_ok, "fail", (0,))])
    res = ClusterSim(
        _sched(cluster), burst_stream([wl], policy="linear"),
        attempt_failures=NoFailures(), failure_process=trace,
        config=SimConfig(checkpoint_interval=t_ok / 10,
                         checkpoint_overhead=t_ok / 200,
                         failure_horizon=10.0 * t_ok),
        rng=np.random.default_rng(1)).run()
    job = res.jobs[0]
    assert job.aborts == 1 and job.finish_time > 0
    # ~5 checkpoints preserved ~half the work: total well below the
    # ~1.55 * t_ok a from-scratch restart would cost.  The bound also
    # polices overhead charging: the restarted attempt (R ~ 0.5 t_ok)
    # must pay for its own ~4 checkpoint writes, not the initial 10.
    assert res.makespan < 1.45 * t_ok
    assert res.makespan > t_ok


def test_requeued_job_finishes_after_recover(cluster):
    """End-to-end drain-then-recover: a 9-rank job on a 9-node cluster
    loses a node (no survivors can hold it), waits in the queue, and
    completes once the node is repaired."""
    topo = TorusTopology((3, 3))
    sch = Scheduler(topo)
    proc = ExponentialLifetimes([4], mtbf=0.5, mttr=1.0)
    sim = ClusterSim(
        sch, burst_stream([halo3d((3, 3, 1))], policy="linear"),
        failure_process=proc,
        config=SimConfig(failure_horizon=2.0, checkpoint_interval=0.1),
        rng=np.random.default_rng(6))
    res = sim.run()
    job = res.jobs[0]
    assert job.finish_time > 0 and not res.truncated
    if job.requeues:
        assert job.aborts >= 1


# ----------------------------------------- heartbeat drain-then-recover
def test_drain_then_undrain_hysteresis():
    topo = TorusTopology((4, 4))
    sch = Scheduler(topo, drain_threshold=0.5)
    bad = np.ones(16, dtype=bool)
    bad[3] = False
    for _ in range(20):
        sch.heartbeat_round(bad)
    assert sch.registry[3].state.value == "drained"
    # node recovers: misses fade below the undrain threshold (0.25)
    good = np.ones(16, dtype=bool)
    for _ in range(100):
        sch.heartbeat_round(good)
    assert sch.registry[3].state.value == "up"


def test_drained_node_excluded_then_reused_after_recovery(cluster):
    """Heartbeat-driven drain keeps a flaky node out of placements; once
    its heartbeats recover, the queue drains onto it again."""
    topo = TorusTopology((2, 2))
    sch = Scheduler(topo, drain_threshold=0.5)
    bad = np.ones(4, dtype=bool)
    bad[0] = False
    for _ in range(10):
        sch.heartbeat_round(bad)
    # 4-rank job cannot run on 3 nodes
    rec = sch.submit(Job(halo3d((2, 2, 1)), distribution="linear"))
    assert rec.state == "pending"
    started = []
    for _ in range(40):
        started += sch.heartbeat_round(np.ones(4, dtype=bool))
    assert rec in started and rec.state == "running"


def test_recover_respects_drain_hysteresis():
    """Repair fixes the outage, not the flakiness evidence: a repaired
    node whose estimate still exceeds the drain threshold comes back
    DRAINED (undrain happens via heartbeat hysteresis, not repair)."""
    from repro.cluster.nodes import NodeState
    topo = TorusTopology((4, 4))
    sch = Scheduler(topo, drain_threshold=0.5)
    bad = np.ones(16, dtype=bool)
    bad[2] = False
    for _ in range(20):
        sch.heartbeat_round(bad)
    assert sch.registry[2].state == NodeState.DRAINED
    sch.registry.mark([2], NodeState.DOWN)       # ...then it actually dies
    sch.recover([2])
    assert sch.registry[2].state == NodeState.DRAINED
    # a node with clean heartbeat history returns straight to UP
    sch.registry.mark([3], NodeState.DOWN)
    sch.recover([3])
    assert sch.registry[3].state == NodeState.UP


def test_heartbeat_events_drive_monitor(cluster):
    """In-sim HEARTBEAT events feed the estimator from ground-truth node
    flakiness (registry.true_outage_p)."""
    sch = _sched(cluster)
    sch.registry.set_outage_probabilities([7], 0.8)
    wl = halo3d((2, 2, 2))
    sim = ClusterSim(
        sch, burst_stream([wl] * 8, policy="linear"),
        attempt_failures=NoFailures(),
        config=SimConfig(heartbeat_interval=0.001),
        rng=np.random.default_rng(8))
    res = sim.run()
    assert not res.truncated
    est = sch.monitor.outage_probabilities()
    assert est[7] > 0.3 and est[:7].max() == 0.0


# ----------------------------------------------------- stream semantics
def test_serial_stream_chains_submissions(cluster):
    sch = _sched(cluster)
    wl = halo3d((2, 2, 2))
    sim = ClusterSim(sch, serial_stream([wl] * 5, policy="linear"),
                     attempt_failures=NoFailures(),
                     rng=np.random.default_rng(0))
    res = sim.run()
    subs = [j.submit_time for j in res.jobs]
    fins = [j.finish_time for j in res.jobs]
    assert subs[0] == 0.0
    assert subs[1:] == fins[:-1], "each instance submits as the prior ends"


def test_max_events_truncates():
    topo = TorusTopology((2, 2))
    sch = Scheduler(topo)
    sim = ClusterSim(sch, burst_stream([halo3d((2, 2, 1))] * 4,
                                       policy="linear"),
                     attempt_failures=NoFailures(),
                     config=SimConfig(max_events=2),
                     rng=np.random.default_rng(0))
    assert sim.run().truncated


def test_fixed_placement_rejects_failure_process():
    topo = TorusTopology((2, 2))
    with pytest.raises(ValueError):
        ClusterSim(Scheduler(topo),
                   serial_stream([halo3d((2, 2, 1))], policy="linear",
                                 fixed_placement=np.arange(4)),
                   failure_process=ExponentialLifetimes([0], mtbf=1.0),
                   config=SimConfig(failure_horizon=10.0))


# ------------------------------------------------------ scenario gates
def test_tofa_beats_linear_in_saturated_queue():
    """At the preset's own size (8x8x8, 48 jobs).  The fast shrink (4x4x4
    with a quarter of the nodes flaky, jobs of up to 18 ranks) leaves
    exclusive allocation no room to steer around faults."""
    out = run_preset("saturated-queue", fast=False, seed=0)
    assert (out["policies"]["tofa"]["mean_completion"]
            < out["policies"]["linear"]["mean_completion"])


def test_tofa_beats_linear_under_correlated_failures():
    out = run_preset("correlated-failures", fast=True, seed=0)
    assert (out["policies"]["tofa"]["mean_completion"]
            < out["policies"]["linear"]["mean_completion"])


def test_fat_tree_preset_runs_on_clos_host():
    out = run_preset("fat-tree", fast=True, seed=0)
    for pol in ("linear", "tofa"):
        row = out["policies"][pol]
        assert row["mean_completion"] > 0 and not row["truncated"]


def test_run_scenario_accepts_topology_instance():
    from repro.core.fattree import FatTreeTopology
    res = run_scenario(lambda: npb_dt_like(8), ("linear", "tofa"),
                       topology=FatTreeTopology(4), n_batches=1,
                       n_instances=5, n_faulty=2, p_f=0.3, seed=0)
    for pol in ("linear", "tofa"):
        assert res[pol].mean_completion > 0
