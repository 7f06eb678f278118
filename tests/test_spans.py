"""Program spans (``repro.core.spans``): one record per public engine
call, attached to its plan and kept in ``recent()``; the placement
path's phase spans, their partition and their profiler annotations."""
import sys
import threading

import numpy as np
import pytest

from repro.core import spans
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.fattree import FatTreeTopology
from repro.core.state import ClusterState
from repro.core.topology import TorusTopology
from repro.workloads.patterns import npb_dt_like

PHASES = ("weights", "candidates", "refine")


def _request(topo, n: int = 40, faulty=(3, 20, 41)) -> PlacementRequest:
    p_f = np.zeros(topo.n_nodes)
    p_f[list(faulty)] = 0.02
    state = ClusterState.healthy(topo.n_nodes).with_outage(p_f)
    return PlacementRequest(comm=npb_dt_like(n).comm, topology=topo,
                            state=state)


def _jax_fattree_place(engine=None):
    """One tofa placement on the jax backend that makes several refine
    dispatches: a k=8 fat-tree served lazily (multilevel path)."""
    pytest.importorskip("jax")
    engine = engine or PlacementEngine(backend="jax", lazy_threshold=0)
    return engine.place(_request(FatTreeTopology(8)), policy="tofa")


class _LoggedSpan(spans.span):
    """A span that also logs its (name, start, end)."""

    log: list = []

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.log.append((self.name, self._t0, self._t0 + self.seconds))


# ----------------------------------------------------------- records
def test_record_attaches_to_plan_and_recent():
    plan = PlacementEngine().place(_request(TorusTopology((4, 4, 4))),
                                   policy="tofa")
    last = spans.recent()[-1]
    assert last.name == "place"
    assert last.spans == plan.spans
    assert plan.spans["weights"][0] == 1
    assert plan.spans["candidates"][0] == 1
    assert "refine" not in plan.spans          # numpy backend: no dispatch


def test_recent_keeps_the_last_256():
    for i in range(spans.RECENT_MAX + 44):
        with spans.record(f"r{i}"):
            pass
    got = spans.recent()
    assert len(got) == spans.RECENT_MAX == 4096
    assert got[0].name == "r44"
    assert got[-1].name == f"r{spans.RECENT_MAX + 43}"


def test_wall_time_is_the_record_total():
    engine = PlacementEngine()
    plan = engine.place(_request(TorusTopology((4, 4, 4))), policy="tofa")
    assert plan.wall_time_s == spans.recent()[-1].total_s > 0
    assert plan.cost_breakdown()["wall_time_s"] == plan.wall_time_s
    moved = engine.replace(plan, failed_nodes=plan.placement[:3])
    assert moved.provenance == "replace-incremental"
    rec = spans.recent()[-1]
    assert rec.name == "replace"
    assert moved.wall_time_s == rec.total_s
    assert moved.spans == rec.spans


def test_replace_fast_path_keeps_the_plan():
    engine = PlacementEngine()
    plan = engine.place(_request(TorusTopology((4, 4, 4))), policy="tofa")
    spare = np.setdiff1d(np.arange(64), plan.placement)[:1]
    assert engine.replace(plan, failed_nodes=spare) is plan
    assert spans.recent()[-1].name == "replace"


def test_span_outside_a_record_adds_to_none():
    before = spans.recent()
    with spans.span("refine", B=1, n=4) as s:
        pass
    assert s.seconds >= 0
    assert spans.recent() == before


# ------------------------------------------------- nesting, threads
def test_nested_public_calls_keep_separate_records():
    engine = PlacementEngine()
    req = _request(TorusTopology((4, 4, 4)))
    with spans.record("outer") as outer:
        plan = engine.place(req, policy="tofa")
    # the outer record sees the call as one span, not its phases
    assert outer.closed.spans == {"place": (1, plan.wall_time_s)}
    assert "weights" in plan.spans
    assert outer.closed.total_s >= plan.wall_time_s


def test_place_many_holds_one_record_per_plan():
    engine = PlacementEngine()
    topo = TorusTopology((4, 4, 4))
    reqs = [_request(topo, 16, faulty=f) for f in ((1,), (2,), (3,))]
    plans = engine.place_many(reqs, policy="tofa")
    recs = spans.recent()[-4:]
    assert [r.name for r in recs] == ["place"] * 3 + ["place_many"]
    for plan, rec in zip(plans, recs):
        assert plan.wall_time_s == rec.total_s
        assert plan.spans == rec.spans
    count, seconds = recs[-1].spans["place"]
    assert count == 3
    assert seconds == pytest.approx(sum(p.wall_time_s for p in plans))
    assert recs[-1].total_s >= seconds


def test_threads_keep_separate_records():
    """Eight threads hold a record each at once and interleave their
    spans; each record gets its own spans and no other's."""
    n_threads = 8
    turn = threading.Barrier(n_threads, timeout=60)
    got = {}

    def work(k):
        with spans.record(f"t{k}") as rec:
            turn.wait()
            for _ in range(50 + k):
                with spans.span(f"t{k}.x"):
                    pass
            turn.wait()
        got[k] = rec.closed

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    for k in range(n_threads):
        assert got[k].spans.keys() == {f"t{k}.x"}
        assert got[k].spans[f"t{k}.x"][0] == 50 + k


# ------------------------------------------------- the jax refine path
def test_refine_prepare_and_wait_partition_refine():
    plan = _jax_fattree_place()
    count, total = plan.spans["refine"]
    assert count > 1
    assert plan.spans["refine.prepare"][0] == count
    assert plan.spans["refine.wait"][0] == count
    parts = plan.spans["refine.prepare"][1] + plan.spans["refine.wait"][1]
    assert parts <= total
    assert parts == pytest.approx(total, rel=0.05, abs=2e-3)


def test_phases_do_not_overlap(monkeypatch):
    monkeypatch.setattr(spans, "span", _LoggedSpan)
    _LoggedSpan.log = []
    plan = _jax_fattree_place()
    log = _LoggedSpan.log
    (_, lo, hi), = [e for e in log if e[0] == "place"]
    phases = sorted((s, e, name) for name, s, e in log if name in PHASES)
    assert {name for _, _, name in phases} == set(PHASES)
    for (_, e0, _), (s1, _, _) in zip(phases, phases[1:]):
        assert e0 <= s1
    assert lo <= phases[0][0] and phases[-1][1] <= hi
    inside = sum(plan.spans[p][1] for p in PHASES)
    assert inside <= plan.wall_time_s


def test_refine_count_matches_the_dispatches(monkeypatch):
    pytest.importorskip("jax")
    from repro.core import mapping_jax
    calls = []
    orig = mapping_jax.refine_many

    def counting(*args, **kw):
        calls.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr(mapping_jax, "refine_many", counting)
    plan = _jax_fattree_place()
    assert len(calls) > 1
    assert plan.spans["refine"][0] == len(calls)


def test_profiler_trace_holds_the_spans(tmp_path):
    jax = pytest.importorskip("jax")
    engine = PlacementEngine(backend="jax", lazy_threshold=0)
    _jax_fattree_place(engine)                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan = _jax_fattree_place(engine)
    finally:
        jax.profiler.stop_trace()
    pb, = tmp_path.rglob("*.xplane.pb")
    prof = jax.profiler.ProfileData.from_file(str(pb))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               {k: v for k, v in ev.stats})
              for plane in prof.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro.")]
    (_, lo, hi, _), = [e for e in events if e[0] == "repro.place"]
    refines = [e for e in events if e[0] == "repro.refine"]
    assert len(refines) == plan.spans["refine"][0]
    for _, s, e, args in refines:
        assert lo <= s <= e <= hi
        assert args["B"] >= 1 and args["n"] >= 1
    assert {a["n"] for *_, a in refines} >= {40}   # the job-wide refine
