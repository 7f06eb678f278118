"""E9 (beyond paper) — epoch-keyed engine caching under state churn.

The ROADMAP north-star (serve placement at high request rates) lives or
dies on one property: a placement against a *slowly-drifting* cluster
must hit warm engine caches, paying matrix derivation only when health
actually changes.  This benchmark drives the drain-sweep cluster (the
flaky-node configuration of ``sim/scenarios.py``'s ``drain-sweep``
preset) through a serving loop — every round one heartbeat poll (with
real estimator jitter) and one placement — while genuine node failures
arrive every ``churn_every`` rounds, and reports:

* ``hit_rate``     — warm fraction of engine weight + memo lookups
                     (``PlacementEngine.cache_hit_rate``); before the
                     versioned-ClusterState API the estimator jitter
                     alone forced a cold derivation *every round*;
* ``epochs``       — distinct state versions minted (should track the
                     churn events, not the heartbeat rate);
* ``place_warm_ms`` / ``place_cold_ms`` — median warm vs post-churn
                     placement latency (the torus's vectorised route walk
                     keeps even the cold ones cheap);
* ``weight_delta_updates`` — how many cold derivations took a row-wise
                     refresh path instead of a full re-derivation (0 on
                     the torus, which derives in full).

``--check`` is the CI gate: ``hit_rate`` must stay >= the committed
floor (0.95) on the drain-sweep preset.  ``--write --label <name>``
appends a trajectory point to ``benchmarks/BENCH_state.json``.

    PYTHONPATH=src python -m benchmarks.state_churn [--fast] [--check]
    PYTHONPATH=src python -m benchmarks.state_churn --write --label pr5
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

from repro.cluster.scheduler import Job, Scheduler
from repro.core.engine import PlacementEngine
from repro.core.topology import TorusTopology
from repro.workloads.patterns import npb_dt_like

BENCH_PATH = pathlib.Path(__file__).parent / "BENCH_state.json"
MIN_HIT_RATE = 0.95


def run_churn(fast: bool = False, seed: int = 0) -> dict:
    """The drain-sweep serving loop; returns one benchmark row."""
    dims = (4, 4, 4) if fast else (6, 6, 6)
    n_flaky = 12 if fast else 40
    rounds = 120 if fast else 250
    churn_every = 30 if fast else 25
    topo = TorusTopology(dims)
    engine = PlacementEngine()
    sch = Scheduler(topo, engine=engine, seed=seed, drain_threshold=0.6)
    rng0 = np.random.default_rng(seed * 401 + 19)       # drain-sweep flavor
    flaky = rng0.choice(topo.n_nodes, n_flaky, replace=False)
    truth = np.zeros(topo.n_nodes)
    truth[flaky] = 0.3
    sch.registry.set_outage_probabilities(flaky, 0.3)
    sch.monitor.simulate_rounds(np.random.default_rng(seed ^ 0x5eed),
                                truth, 400)
    reply_rng = np.random.default_rng(seed * 77 + 5)
    wl = npb_dt_like(12 if fast else 16)
    # churn alternates flaky victims (pattern-preserving: the weight
    # matrix is literally unchanged, only the epoch moves) and healthy
    # victims (pattern flip: a fresh Eq. (1) derivation)
    healthy = np.setdiff1d(np.arange(topo.n_nodes), flaky)
    victims = np.empty(2 * min(len(flaky), len(healthy)), dtype=np.int64)
    victims[0::2] = flaky[:len(victims) // 2]
    victims[1::2] = healthy[:len(victims) // 2]
    down: list[int] = []
    epochs = set()
    warm_s: list[float] = []
    cold_s: list[float] = []
    churned = False
    for r in range(rounds):
        alive = np.ones(topo.n_nodes, dtype=bool)
        alive[down] = False
        replies = alive & (reply_rng.random(topo.n_nodes) >= truth)
        sch.heartbeat_round(replies)
        if (r + 1) % churn_every == 0 and len(down) < len(victims):
            victim = int(victims[len(down)])
            down.append(victim)
            sch.handle_node_failure([victim])
            churned = True
        t0 = time.perf_counter()
        rec = sch.submit(Job(wl, distribution="tofa"))
        dt = time.perf_counter() - t0
        (cold_s if churned else warm_s).append(dt)
        churned = False
        assert rec.state == "running"
        sch.complete(rec.job.job_id)
        epochs.add(sch.cluster_state().epoch)
    stats = engine.cache_stats()
    return {
        "preset": "drain-sweep",
        "dims": list(dims),
        "rounds": rounds,
        "churn_events": len(down),
        "placements": rounds,
        "epochs": len(epochs),
        "hit_rate": engine.cache_hit_rate(),
        "place_warm_ms": 1e3 * float(np.median(warm_s)),
        "place_cold_ms": (1e3 * float(np.median(cold_s))
                          if cold_s else None),
        "weight_misses": stats["weight_misses"],
        "weight_hits": stats["weight_hits"],
        "shared_misses": stats["shared_misses"],
        "shared_hits": stats["shared_hits"],
        "weight_delta_updates": stats["weight_delta_updates"],
        "place_time_s": sch.place_time_s,
    }


def run(csv=print, fast: bool = False, seed: int = 0) -> dict:
    t0 = time.perf_counter()
    row = run_churn(fast=fast, seed=seed)
    wall = time.perf_counter() - t0
    csv(f"state_churn,{row['preset']},hit_rate,{row['hit_rate']:.4f},frac,"
        f"epochs={row['epochs']},churn={row['churn_events']},"
        f"placements={row['placements']},"
        f"delta_updates={row['weight_delta_updates']}")
    cold = (f"{row['place_cold_ms']:.1f}" if row['place_cold_ms'] is not None
            else "n/a")
    csv(f"state_churn,{row['preset']},place_warm_ms,"
        f"{row['place_warm_ms']:.1f},ms,cold_ms={cold}")
    csv(f"state_churn,{row['preset']},wall_time,{wall:.1f},s")
    return row


def check(row: dict) -> int:
    ok = row["hit_rate"] >= MIN_HIT_RATE
    print(f"GATE drain-sweep churn: hit_rate={row['hit_rate']:.4f} "
          f"(floor {MIN_HIT_RATE}) {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


def write_trajectory(row: dict, label: str, fast: bool) -> None:
    doc = {"schema": 1,
           "gate": {"preset": "drain-sweep", "min_hit_rate": MIN_HIT_RATE},
           "trajectory": []}
    if BENCH_PATH.exists():
        doc = json.loads(BENCH_PATH.read_text())
    doc["trajectory"].append({"label": label, "fast": fast, "presets": [row]})
    BENCH_PATH.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"appended trajectory point {label!r} to {BENCH_PATH}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when the engine cache hit rate "
                         "falls below the committed floor")
    ap.add_argument("--write", action="store_true",
                    help="append a point to BENCH_state.json")
    ap.add_argument("--label", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    row = run(fast=args.fast, seed=args.seed)
    if args.write:
        write_trajectory(row, args.label or "unlabeled", bool(args.fast))
    return check(row) if args.check else 0


if __name__ == "__main__":
    sys.exit(main())
