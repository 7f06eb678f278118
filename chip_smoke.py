"""Smoke run of the served placement path on a TPU, through the jax backend.

One chip (the default) drives the path a user calls at the paper's
deployment size, with the jax backend pinned to one device:

  a. ``PlacementService`` serves an open-loop stream of about 200
     interactive / standard / best-effort requests on an 8x8x8 torus
     (512 nodes) under flaky-node churn (the ``benchmarks.serve_storm``
     stream and churn builders).  Every request must end placed,
     completed or shed, and every lease must be valid when it starts:
     distinct node ids, none DOWN, none held by another lease.
  b. ``engine.place(policy="tofa")`` of an all-to-all guest (256 ranks)
     onto the 8x8x8 torus with faulty nodes: the dense-guest refine, whose
     swap step is the Pallas ``swap_select`` kernel.
  c. LAMMPS-like guest (1024 ranks) onto a healthy 16x16x32 torus (8192
     nodes, above the engine's lazy threshold): distances computed
     in-kernel by the Pallas ``torus_hop`` kernel.
  d. NPB-DT-like guest (512 ranks) onto a k=28 fat-tree (5488 hosts) with
     faulty hosts: the Pallas ``fattree_hop`` kernel.
  e. NPB-DT-like and LAMMPS-like guests (512 ranks) onto a k=48 fat-tree
     (27,648 hosts) and onto Titan's 25x16x24x2 torus (19,200 nodes), 16
     faulty nodes each: every lane of each multilevel level's one-dispatch
     sub-problem refine (``mapping_jax.refine_subproblems``) must equal
     the sub-problem's own ``refine_many`` dispatch, at the chip's float32.

Phases b-d are checked against the numpy backend's placement of the same
request: the chip's placement must be valid and its hop-bytes, evaluated
on the host in float64 under the metric the refiner minimises, no more
than 1% above numpy's.  Each phase must dispatch its refine to the device,
with no numpy fallback and no sharded dispatch, and the programs of b-d
must contain a Pallas call (``tpu_custom_call``).

``--chips 4`` runs only the path that exists across chips: the sharded
candidate-stack refine (``mapping_jax.refine_many``) on 4 devices against
the same stacks on 1 device, at the sizes of phases b-d.  The placements
must be bit-identical and ``sharded_dispatches`` must increase.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every check passed.  Without a TPU the script exits
non-zero before any phase runs.

    python chip_smoke.py              # one chip, phases a-e
    python chip_smoke.py --chips 4    # sharded refine on four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
MAX_HOP_BYTES_EXCESS = 0.01     # chip hop-bytes <= (1 + this) * numpy's
N_FAULTY = 8                    # faulty nodes of phases b and d
FAULT_P = 0.05                  # their outage belief
N_FAULTY_E = 16                 # faulty nodes of phase e, at outage
FAULT_P_E = 0.02                # belief 0.02


class SmokeFailure(RuntimeError):
    pass


def require_tpu(count: int) -> dict:
    """The device record of the last line (``count`` is set to the
    devices used once the phases have run); raises unless JAX sees at
    least ``count`` TPU devices.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < count:
        raise SmokeFailure(f"{count} chips asked for, {len(devices)} seen")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _report(phase: str, row: dict) -> None:
    print(f"{phase}: {json.dumps(row, sort_keys=True)}", flush=True)


class _RefineRecorder:
    """Counts the device refine dispatches and keeps the last one's
    arguments, so the program it compiled can be lowered again and read."""

    def __init__(self):
        from repro.core import mapping_jax
        self._mj = mapping_jax
        self._orig = mapping_jax.refine_many
        self.calls = 0
        self.last = None

    def __enter__(self):
        def recorded(G_w, D, placements, *args, **kw):
            self.calls += 1
            if kw.get("lane") is None:       # a dispatch of its own
                self.last = (G_w, D, placements, args, kw)
            return self._orig(G_w, D, placements, *args, **kw)
        self._mj.refine_many = recorded
        return self

    def __exit__(self, *exc):
        self._mj.refine_many = self._orig

    def has_pallas_call(self) -> bool:
        """Whether the last refine's program contains a Pallas kernel."""
        from repro.core import backend
        if self.last is None:
            return False
        G_w, D, placements, args, kw = self.last
        be = backend.get_backend("jax")
        with backend.use("jax"), be.scope():
            run, operands, _ = self._mj.refine_program(G_w, D, placements,
                                                       *args, **kw)
            return "tpu_custom_call" in run.lower(*operands).as_text()


def _jax_stats() -> dict:
    from repro.core import backend
    return dict(backend.get_backend("jax").stats)


def _stat_delta(before: dict, key: str) -> int:
    return _jax_stats()[key] - before[key]


# ---------------------------------------------------------------- phase a
def run_service(dims=(8, 8, 8), n_req: int = 200, rate: float = 10.0,
                n_flaky: int = 24, seed: int = 0, reps: int = 2) -> dict:
    """Serve one open-loop stream ``reps`` times, each with a fresh
    service and engine (the first run compiles, the later ones reuse)."""
    from benchmarks.serve_storm import build_churn, build_stream
    from repro.core.engine import PlacementEngine
    from repro.core.state import NodeHealth
    from repro.core.topology import TorusTopology
    from repro.service import PlacementService

    topo = TorusTopology(tuple(dims))
    flaky, belief, failures, recoveries = build_churn(
        topo, n_flaky=n_flaky, seed=seed, horizon=n_req / rate + 60.0,
        churn_every=5.0, repair_after=15.0, per_event=4)
    bad: list[str] = []

    class CheckedLog(list):
        """The service's placement log, checking each lease as it starts."""

        def __init__(self, svc):
            super().__init__()
            self.svc = svc

        def append(self, entry):
            req_id, nodes = entry
            ids = np.asarray(nodes, dtype=np.int64)
            if len(set(nodes)) != len(nodes):
                bad.append(f"request {req_id}: repeated node ids")
            if (self.svc.state.health[ids] == int(NodeHealth.DOWN)).any():
                bad.append(f"request {req_id}: placed on a DOWN node")
            if np.isin(ids, self.svc.busy_nodes(exclude=req_id)).any():
                bad.append(f"request {req_id}: node held by another lease")
            super().append(entry)

    reqs = build_stream(n_req, rate, seed)     # one set of request ids
    runs, logs = [], []
    stats0 = _jax_stats()
    with _RefineRecorder() as rec:
        for _ in range(reps):
            svc = PlacementService(topo, engine=PlacementEngine(
                backend="jax"), policy="tofa", seed=seed,
                drain_interval=0.25, restart_delay=1.0)
            svc.placement_log = CheckedLog(svc)
            t0 = time.perf_counter()
            res = svc.run(reqs, failures=failures, recoveries=recoveries,
                          heartbeat_interval=0.5, belief=belief,
                          belief_jitter=0.3)
            runs.append((time.perf_counter() - t0, res))
            logs.append(list(res.placement_log))
        pallas = rec.has_pallas_call()
    res = runs[-1][1]
    statuses = {}
    for reply in res.replies.values():
        statuses[reply.status] = statuses.get(reply.status, 0) + 1
    ended = sum(statuses.get(s, 0) for s in ("placed", "completed", "shed"))
    return {
        "requests": len(reqs), "submitted": res.row["submitted"],
        "statuses": statuses, "all_ended": ended == len(reqs),
        "placements": len(res.placement_log),
        "replaced": res.row["replaced"],
        "churn_events": len(failures), "invalid_leases": bad[:5],
        "n_invalid_leases": len(bad),
        "deterministic": all(log == logs[0] for log in logs),
        "cold_s": runs[0][0], "warm_s": runs[-1][0],
        "hit_rate": res.hit_rate, "refine_calls": rec.calls,
        "tpu_custom_call": pallas,
        "numpy_fallbacks": _stat_delta(stats0, "numpy_fallbacks"),
        "sharded_dispatches": _stat_delta(stats0, "sharded_dispatches"),
    }


def check_service(row: dict) -> list[str]:
    out = []
    if row["submitted"] != row["requests"] or not row["all_ended"]:
        out.append(f"requests did not all end placed, completed or shed: "
                   f"{row['statuses']}")
    if row["n_invalid_leases"]:
        out.append(f"invalid leases: {row['invalid_leases']}")
    if not row["deterministic"]:
        out.append("placement logs differ between equal-seed runs")
    return out + _check_dispatch(row)


# ------------------------------------------------------------ phases b-d
def _faulty_state(n_nodes: int, n_faulty: int, seed: int,
                  p: float = FAULT_P):
    from repro.core.state import ClusterState
    p_f = np.zeros(n_nodes)
    rng = np.random.default_rng(seed)
    p_f[rng.choice(n_nodes, n_faulty, replace=False)] = p
    return ClusterState.healthy(n_nodes).with_outage(p_f)


def run_placement(topo, workload, state=None, reps: int = 3,
                  lazy_threshold=None) -> dict:
    """Place ``workload`` onto ``topo`` ``reps`` times with the jax
    backend (rng seeds 0, 1, ...) and once per seed with numpy."""
    from repro.core import backend, mapping
    from repro.core.engine import PlacementEngine, PlacementRequest
    from repro.core.state import ClusterState

    state = state or ClusterState.healthy(topo.n_nodes)
    req = PlacementRequest(comm=workload.comm, topology=topo, state=state)
    G = req.comm.weights(req.metric)
    chip = PlacementEngine(backend="jax", lazy_threshold=lazy_threshold)
    ref = PlacementEngine(backend="numpy", lazy_threshold=lazy_threshold)
    stats0 = _jax_stats()
    times, plans = [], []
    with _RefineRecorder() as rec:
        for s in range(reps):
            t0 = time.perf_counter()
            # the plan's placement is a host array copied back from the
            # device, so the clock stops after the device has finished
            plans.append(chip.place(req, policy="tofa",
                                    rng=np.random.default_rng(s)))
            times.append(time.perf_counter() - t0)
        pallas = rec.has_pallas_call()
    calls = rec.calls
    fallbacks = _stat_delta(stats0, "numpy_fallbacks")
    sharded = _stat_delta(stats0, "sharded_dispatches")
    # the metric tofa's refine minimises: Eq. 1 route weights
    W = ref.weights(topo, req.route_p_f(), req.straggler)
    avail = set(state.available_ids().tolist())
    identical, rel, valid = 0, [], True
    with backend.use("numpy"):
        for s, plan in enumerate(plans):
            base = ref.place(req, policy="tofa",
                             rng=np.random.default_rng(s))
            p = plan.placement
            valid &= (len(p) == req.n_procs and len(set(p.tolist())) == len(p)
                      and set(p.tolist()) <= avail)
            identical += bool(np.array_equal(p, base.placement))
            hb = mapping.hop_bytes(G, W, p)
            hb_ref = mapping.hop_bytes(G, W, base.placement)
            rel.append((hb - hb_ref) / hb_ref)
    return {
        "nodes": topo.n_nodes, "ranks": req.n_procs,
        "faulty": int((state.p_f > 0).sum()),
        "dtype": backend.get_backend("jax").dtype,
        "cold_s": times[0], "warm_s": float(np.median(times[1:] or times)),
        "identical_share": identical / reps, "valid": bool(valid),
        "hop_bytes_rel_to_numpy": rel, "refine_calls": calls,
        "tpu_custom_call": pallas, "numpy_fallbacks": fallbacks,
        "sharded_dispatches": sharded,
    }


def _check_dispatch(row: dict) -> list[str]:
    out = []
    if not row["refine_calls"]:
        out.append("no refine was dispatched to the device")
    if row["numpy_fallbacks"]:
        out.append(f"{row['numpy_fallbacks']} calls fell back to numpy")
    if row["sharded_dispatches"]:
        out.append(f"{row['sharded_dispatches']} sharded dispatches on "
                   f"one chip")
    return out


def check_placement(row: dict, want_pallas: bool = True) -> list[str]:
    out = _check_dispatch(row)
    if not row["valid"]:
        out.append("invalid placement")
    worst = max(row["hop_bytes_rel_to_numpy"])
    if worst > MAX_HOP_BYTES_EXCESS:
        out.append(f"hop-bytes {worst:+.4%} above numpy's")
    if want_pallas and not row["tpu_custom_call"]:
        out.append("the refine program has no Pallas call")
    return out


# ---------------------------------------------------------------- phase e
def run_subproblem_lanes(topo, workload, state=None,
                         lazy_threshold=None) -> dict:
    """One tofa placement of ``workload`` with the jax backend; then each
    multilevel level's sub-problems, as its one dispatch was given them,
    are refined again by one ``refine_many`` dispatch each, and every
    lane is compared with its own dispatch."""
    from repro.core import backend, mapping_jax
    from repro.core.engine import PlacementEngine, PlacementRequest
    from repro.core.state import ClusterState

    state = state or ClusterState.healthy(topo.n_nodes)
    req = PlacementRequest(comm=workload.comm, topology=topo, state=state)
    levels = []
    orig = mapping_jax.refine_subproblems

    def recorded(G_w, D, groups, placements, *args, **kw):
        inputs = [np.array(p) for p in placements]
        out = orig(G_w, D, groups, placements, *args, **kw)
        levels.append((G_w, D, list(groups), inputs, out))
        return out

    mapping_jax.refine_subproblems = recorded
    try:
        t0 = time.perf_counter()
        PlacementEngine(backend="jax", lazy_threshold=lazy_threshold).place(
            req, policy="tofa", rng=np.random.default_rng(0))
        place_s = time.perf_counter() - t0
    finally:
        mapping_jax.refine_subproblems = orig
    lanes = equal = moved = 0
    with backend.use("jax"):
        for G_w, D, groups, inputs, out in levels:
            for F, p, got in zip(groups, inputs, out):
                alone = mapping_jax.refine_many(G_w[np.ix_(F, F)], D,
                                                p[None])[0]
                lanes += 1
                equal += bool(np.array_equal(got, alone))
                moved += not np.array_equal(got, p)
    return {"nodes": topo.n_nodes, "ranks": req.n_procs,
            "faulty": int((state.p_f > 0).sum()),
            "dtype": backend.get_backend("jax").dtype,
            "place_s": place_s, "levels": len(levels),
            "widths": sorted({len(F) for _, _, g, _, _ in levels
                              for F in g}),
            "lanes": lanes, "lanes_equal": equal, "lanes_moved": moved}


def check_subproblem_lanes(row: dict) -> list[str]:
    out = []
    if not row["lanes"]:
        out.append("no multilevel sub-problem was batched")
    if row["lanes_equal"] != row["lanes"]:
        out.append(f"{row['lanes'] - row['lanes_equal']} of {row['lanes']} "
                   f"batched lanes differ from their own dispatch")
    return out


def subproblem_phases() -> dict:
    """Phase e: the benchmark's 512-rank guests on its two large
    fabrics."""
    from repro.core.fattree import FatTreeTopology
    from repro.core.topology import TorusTopology
    from repro.workloads.patterns import lammps_like, npb_dt_like
    phases = {}
    for fabric, topo in (("fattree-k48", FatTreeTopology(48)),
                         ("titan", TorusTopology((25, 16, 24, 2)))):
        state = _faulty_state(topo.n_nodes, N_FAULTY_E, seed=3,
                              p=FAULT_P_E)
        for guest, wl in (("npb-dt", npb_dt_like(512)),
                          ("lammps", lammps_like(512))):
            name = f"e-lanes-{fabric}-{guest}"
            phases[name] = run_subproblem_lanes(topo, wl, state)
            _report(name, phases[name])
    return phases


def one_chip_phases() -> dict:
    """Phases a-e at the paper's deployment size."""
    from repro.core.fattree import FatTreeTopology
    from repro.core.topology import TorusTopology
    from repro.workloads.patterns import (alltoall_heavy, lammps_like,
                                          npb_dt_like)
    torus = TorusTopology((8, 8, 8))
    big = TorusTopology((16, 16, 32))
    ft = FatTreeTopology(28)
    phases = {"a-service": run_service()}
    _report("a-service", phases["a-service"])
    for name, topo, wl, state in (
            ("b-dense-torus", torus, alltoall_heavy(256),
             _faulty_state(torus.n_nodes, N_FAULTY, seed=1)),
            ("c-implicit-torus", big, lammps_like(1024), None),
            ("d-implicit-fattree", ft, npb_dt_like(512),
             _faulty_state(ft.n_nodes, N_FAULTY, seed=2))):
        phases[name] = run_placement(topo, wl, state)
        _report(name, phases[name])
    phases.update(subproblem_phases())
    return phases


def check_one_chip(phases: dict) -> list[str]:
    out = [f"a-service: {m}" for m in check_service(phases["a-service"])]
    for name, row in phases.items():
        check = (check_subproblem_lanes if name.startswith("e-")
                 else check_placement)
        if name != "a-service":
            out += [f"{name}: {m}" for m in check(row)]
    return out


# ----------------------------------------------------------- four chips
def sharded_stacks(batch: int = 8, seed: int = 0) -> list:
    """``(name, G_w, D, placements)`` candidate stacks at the sizes of
    phases b-d: random starting placements, so every refine works."""
    from repro.core.fattree import FatTreeTopology
    from repro.core.topology import TorusTopology
    from repro.workloads.patterns import (alltoall_heavy, lammps_like,
                                          npb_dt_like)
    rng = np.random.default_rng(seed)
    torus = TorusTopology((8, 8, 8))
    big = TorusTopology((16, 16, 32))
    ft = FatTreeTopology(28)
    p_t = _faulty_state(torus.n_nodes, N_FAULTY, seed=1).p_f
    p_ft = _faulty_state(ft.n_nodes, N_FAULTY, seed=2).p_f
    cases = [("dense-torus", alltoall_heavy(256), torus.weight_matrix(p_t),
              torus.n_nodes),
             ("implicit-torus", lammps_like(1024), big.lazy_distance(),
              big.n_nodes),
             ("implicit-fattree", npb_dt_like(512), ft.lazy_distance(p_ft),
              ft.n_nodes)]
    out = []
    for name, wl, D, n_nodes in cases:
        P = np.stack([rng.permutation(n_nodes)[:wl.comm.n]
                      for _ in range(batch)])
        out.append((name, wl.comm.G_v, D, P))
    return out


def run_sharded(stacks, n_dev: int) -> dict:
    """Each stack refined on ``n_dev`` devices and on one."""
    from repro.core import backend, mapping_jax
    rows = {}
    for name, G, D, P in stacks:
        row = {"candidates": len(P), "ranks": P.shape[1]}
        outs = {}
        for dev in (1, n_dev):
            with backend.use("jax", devices=dev) as be:
                before = be.stats["sharded_dispatches"]
                times = []
                for _ in range(2):
                    t0 = time.perf_counter()
                    outs[dev] = mapping_jax.refine_many(G, D, P)
                    times.append(time.perf_counter() - t0)
                row[f"devices{dev}"] = {
                    "cold_s": times[0], "warm_s": times[1],
                    "sharded_dispatches":
                        be.stats["sharded_dispatches"] - before}
        row["dtype"] = backend.get_backend("jax").dtype
        row["bit_identical"] = bool(np.array_equal(outs[1], outs[n_dev]))
        rows[name] = row
        _report(f"sharded-{name}", row)
    return rows


def check_sharded(rows: dict, n_dev: int) -> list[str]:
    out = []
    for name, row in rows.items():
        if not row["bit_identical"]:
            out.append(f"{name}: {n_dev}-device placements differ from "
                       f"one device's")
        if row[f"devices{n_dev}"]["sharded_dispatches"] < 1:
            out.append(f"{name}: sharded_dispatches did not increase")
        if row["devices1"]["sharded_dispatches"]:
            out.append(f"{name}: one device dispatched sharded")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-e on one device; 4: only the "
                         "sharded refine, against one device")
    args = ap.parse_args(argv)
    if args.chips == 1:
        os.environ["REPRO_JAX_DEVICES"] = "1"     # pin one device
    try:
        device = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.core import backend
    if args.chips == 1:
        failures = check_one_chip(one_chip_phases())
        used = backend.get_backend("jax").device_count
    else:
        failures = check_sharded(run_sharded(sharded_stacks(), args.chips),
                                 args.chips)
        used = args.chips
    # the devices the run used, not all the host shows
    device["count"] = used
    for msg in failures:
        print(f"chip_smoke: FAIL {msg}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
