"""E8 (beyond paper) — event-driven cluster-simulator scenario sweep.

Runs the scenario presets (``repro.sim.scenarios``) per policy and emits
one CSV row per (scenario, policy) with mean job completion, makespan,
abort and event counts, and the scheduler's aggregate ``place_time_s``
(mapper wall-clock across batched ``place_many`` queue drains and
fault-driven re-placements — the number the batched drain shrinks).
``--write --label <name>`` appends a point to the committed
``benchmarks/BENCH_clustersim.json`` trajectory.

``--check`` is a *statistical* gate: each gated preset is executed across
``--replicas`` independent seeds (default 64; the committed trajectory
carries >= 1000-replica points) through :mod:`repro.sim.replicas`, and
the gate passes only when the 95% percentile-bootstrap CI of the paired
per-seed delta ``mean_completion(linear) - mean_completion(tofa)`` lies
strictly above zero.  Single-seed point comparisons were retired after a
64-seed audit (see ``SEED_AUDIT``) showed presets flip their tofa<linear
verdict on a minority of seeds — the paired CI is stable where the
anecdote is not.  Replica rows grow additive
``n_replicas``/``ci_low``/``ci_high``/``win_rate`` keys next to the
existing schema.

    PYTHONPATH=src python -m benchmarks.clustersim [--fast] [--check]
    PYTHONPATH=src python -m benchmarks.clustersim --fast --check \
        --replicas 64 --presets cascading-racks,maintenance-burst --skip-sweep
    PYTHONPATH=src python -m benchmarks.clustersim --fast --write \
        --label pr8 --replicas 1000
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

from repro.sim.replicas import run_replicas
from repro.sim.scenarios import run_preset

BENCH_PATH = pathlib.Path(__file__).parent / "BENCH_clustersim.json"
# saturated-queue is not gated: its fast shrink (4x4x4, a quarter of the
# nodes flaky, jobs of up to 18 ranks) leaves exclusive allocation no room
# to steer around faults, and the 64-seed audit below is a coin flip.
GATED = ("correlated-failures", "degraded-drain", "cascading-racks",
         "maintenance-burst")
PRESETS = ("paper-fig4-5", "saturated-queue", "mixed-stream", "fat-tree",
           "correlated-failures", "drain-sweep", "degraded-drain",
           "dragonfly", "cascading-racks", "maintenance-burst")

# 64-seed fast-mode audit (seed 0..63, single-seed tofa<linear verdicts;
# a flip is a seed where tofa's mean completion is not below linear's),
# taken with exclusive allocation.  Counts are committed with each
# replica trajectory point so the gate's rationale travels with the data.
SEED_AUDIT = {
    "saturated-queue": {"n_seeds": 64, "verdict_flips": 30},
    "correlated-failures": {"n_seeds": 64, "verdict_flips": 8},
    "degraded-drain": {"n_seeds": 64, "verdict_flips": 6},
    "cascading-racks": {"n_seeds": 64, "verdict_flips": 6},
    "maintenance-burst": {"n_seeds": 64, "verdict_flips": 8},
}


def _flat_rows(name: str, out: dict) -> list[dict]:
    """Flatten a preset result into per-(policy[, threshold]) rows."""
    rows = []
    for pol, row in out["policies"].items():
        if "mean_completion" in row:
            rows.append(dict(
                scenario=name, policy=pol,
                mean_completion=row["mean_completion"],
                makespan=row.get("makespan", row["mean_completion"]),
                aborted_attempts=row["aborted_attempts"],
                n_events=row["n_events"],
                truncated=row.get("truncated", False),
                place_time_s=row.get("place_time_s", 0.0)))
        else:   # drain-sweep: one row per threshold
            for th, r in row.items():
                rows.append(dict(scenario=f"{name}/th={th}", policy=pol,
                                 mean_completion=r["mean_completion"],
                                 makespan=r["makespan"],
                                 aborted_attempts=r["aborted_attempts"],
                                 n_events=r["n_events"],
                                 truncated=r.get("truncated", False),
                                 place_time_s=r.get("place_time_s", 0.0)))
    return rows


def run(csv=print, fast: bool | None = None, seed: int = 0) -> dict:
    if fast is None:
        fast = bool(int(os.environ.get("FAST", "0")))
    all_rows: list[dict] = []
    summary: dict = {}
    for name in PRESETS:
        t0 = time.perf_counter()
        out = run_preset(name, seed=seed, fast=fast)
        wall = time.perf_counter() - t0
        rows = _flat_rows(name, out)
        all_rows += rows
        summary[name] = out
        for r in rows:
            csv(f"clustersim,{r['scenario']},{r['policy']},"
                f"{r['mean_completion']:.4f},s_mean_completion,"
                f"makespan={r['makespan']:.4f},"
                f"aborts={r['aborted_attempts']},events={r['n_events']},"
                f"place_time_s={r['place_time_s']:.4f}")
        csv(f"clustersim,{name},wall_time,{wall:.1f},s")
    for name in GATED:
        pols = summary[name]["policies"]
        imp = 1.0 - (pols["tofa"]["mean_completion"]
                     / pols["linear"]["mean_completion"])
        csv(f"clustersim,{name},tofa_improvement,{imp:.3f},frac")
    summary["_rows"] = all_rows
    return summary


def run_replica_rows(presets, n_replicas: int, *, fast: bool,
                     base_seed: int = 0, B: int = 2000,
                     alpha: float = 0.05, executor: str = "auto",
                     max_workers=None, csv=print) -> tuple[list[dict], dict]:
    """Replica-mode sweep: per-policy bootstrap rows + paired comparisons.

    Returns (rows, comparisons): rows use the single-seed schema plus the
    additive ``n_replicas``/``ci_low``/``ci_high``/``win_rate`` keys
    (win_rate only on the non-baseline policy row); comparisons maps
    preset name -> :class:`repro.sim.replicas.PairedComparison`.
    """
    rows: list[dict] = []
    comparisons: dict = {}
    for name in presets:
        t0 = time.perf_counter()
        rs = run_replicas(name, n_replicas=n_replicas, base_seed=base_seed,
                          fast=fast, executor=executor,
                          max_workers=max_workers)
        wall = time.perf_counter() - t0
        cmp = rs.compare(B=B, alpha=alpha)
        comparisons[name] = cmp
        for pol in rs.policies:
            s = rs.summary(pol, B=B, alpha=alpha)
            mk = rs.metrics[pol].get("makespan",
                                     rs.metrics[pol]["mean_completion"])
            trunc = rs.metrics[pol].get("truncated")
            rows.append(dict(
                scenario=name, policy=pol,
                mean_completion=s.mean,
                makespan=float(mk.mean()),
                aborted_attempts=float(
                    rs.metrics[pol]["aborted_attempts"].mean()),
                n_events=float(rs.metrics[pol]["n_events"].mean()),
                truncated=bool(trunc is not None and trunc.any()),
                place_time_s=float(
                    rs.metrics[pol].get("place_time_s",
                                        mk * 0.0).mean()),
                n_replicas=rs.n_replicas,
                ci_low=s.ci_low, ci_high=s.ci_high,
                win_rate=cmp.win_rate if pol == cmp.a else None))
            csv(f"clustersim,{name},{pol},{s.mean:.4f},"
                f"s_mean_completion,n_replicas={rs.n_replicas},"
                f"ci=[{s.ci_low:.4f},{s.ci_high:.4f}]")
        csv(f"clustersim,{name},delta,{cmp.delta:.4f},s,"
            f"ci=[{cmp.delta_ci_low:.4f},{cmp.delta_ci_high:.4f}],"
            f"win_rate={cmp.win_rate:.3f},p={cmp.p_value:.4g},"
            f"wall={wall:.1f}s")
    return rows, comparisons


def check_replicas(comparisons: dict, rows: list[dict]) -> int:
    """Statistical CI gate: paired delta CI above zero, no truncation."""
    rc = 0
    truncated = {r["scenario"] for r in rows
                 if r.get("n_replicas") and r["truncated"]}
    for name, cmp in comparisons.items():
        ok = cmp.significant
        print(f"GATE {name}: n={cmp.n} tofa={cmp.mean_a:.4f} "
              f"linear={cmp.mean_b:.4f} "
              f"delta={cmp.delta:.4f} "
              f"ci=[{cmp.delta_ci_low:.4f},{cmp.delta_ci_high:.4f}] "
              f"win_rate={cmp.win_rate:.3f} p={cmp.p_value:.4g} "
              f"{'OK' if ok else 'FAIL'}")
        if not ok:
            rc = 1
        if name in truncated:
            print(f"GATE {name}: FAIL (a replica hit max_events budget)")
            rc = 1
    return rc


def write_trajectory(rows: list[dict], label: str, fast: bool,
                     n_replicas: int | None = None) -> None:
    doc = {"schema": 1, "trajectory": []}
    if BENCH_PATH.exists():
        doc = json.loads(BENCH_PATH.read_text())
    point = {"label": label, "fast": fast, "allocation": "exclusive",
             "scenarios": rows}
    if n_replicas:
        point["n_replicas"] = n_replicas
        point["seed_audit"] = SEED_AUDIT
    doc["trajectory"].append(point)
    BENCH_PATH.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"appended trajectory point {label!r} to {BENCH_PATH}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the paired bootstrap CI of "
                         "linear-minus-tofa is above zero on every gated "
                         "preset")
    ap.add_argument("--write", action="store_true",
                    help="append a point to BENCH_clustersim.json")
    ap.add_argument("--label", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="single-seed sweep seed / replica base seed")
    ap.add_argument("--replicas", type=int, default=None,
                    help="Monte-Carlo replicas per gated preset "
                         "(--check defaults to 64)")
    ap.add_argument("--presets", default=None,
                    help="comma list restricting the replica sweep "
                         "(default: the gated presets)")
    ap.add_argument("--bootstrap", type=int, default=2000,
                    help="bootstrap resamples B")
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--executor", default="auto",
                    choices=("auto", "serial", "process"))
    ap.add_argument("--workers", "--jobs", dest="workers", type=int,
                    default=None,
                    help="process-pool workers for the replica sweep; "
                         "0 (or omitted) auto-detects os.cpu_count()")
    ap.add_argument("--skip-sweep", action="store_true",
                    help="skip the single-seed CSV sweep (replica-only run)")
    args = ap.parse_args()
    if args.check and args.replicas is None:
        args.replicas = 64
    rows: list[dict] = []
    if not args.skip_sweep:
        rows += run(fast=args.fast or None, seed=args.seed)["_rows"]
    comparisons: dict = {}
    if args.replicas:
        presets = (tuple(p for p in args.presets.split(",") if p)
                   if args.presets else GATED)
        rep_rows, comparisons = run_replica_rows(
            presets, args.replicas, fast=bool(args.fast),
            base_seed=args.seed, B=args.bootstrap, alpha=args.alpha,
            executor=args.executor, max_workers=args.workers)
        rows += rep_rows
    if args.write:
        write_trajectory(rows, args.label or "unlabeled", bool(args.fast),
                         n_replicas=args.replicas)
    if args.check:
        return check_replicas(comparisons, rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
