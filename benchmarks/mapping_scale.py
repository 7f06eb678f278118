"""E4 (beyond paper) — mapper cost/quality scaling + engine cache ablation.

Hop-bytes quality and wall-clock of the Scotch-analogue mapper vs greedy /
random / linear across process counts and torus sizes — establishes that
TOFA placement overhead stays negligible against job runtimes — plus a
cached-vs-uncached comparison of fault-aware placement latency: the
PlacementEngine derives the Eq. 1 route-weight matrix once per
(topology, health) state, so every subsequent placement against the same
health snapshot skips the dominant cost.

``--backend jax`` (or ``run(backend="jax")``) measures the same matrix
under the jitted jax placement backend (``repro.core.backend``) —
placements are identical, so any wall-clock delta is pure backend cost.

Implicit-distance scaling axis (PR 7)::

    ... mapping_scale --implicit            # 16k-, 64k- and 128k-node
        implicit-torus placements, one subprocess per row so peak-RSS is
        per-case; each row also times an incremental ``engine.replace``
        after killing 4 used nodes (the lazy-exact re-placement path)
    ... mapping_scale --implicit --fast     # CI smoke: the 16k-node case
        must finish under a machine-normalised wall budget AND peak RSS
        must stay below the bytes a dense N x N hop matrix alone would
        take (proof the lazy path never densifies); the 128k-node leg
        then runs under the same gates, but only when its predicted wall
        fits IMPLICIT_128K_GUARD_S on this machine
    ... mapping_scale --scale --write       # append a trajectory point to
        benchmarks/BENCH_mapping.json: the refine_scale case matrix plus
        implicit rows carrying additive keys peak_rss_bytes / lazy /
        backend / dense_matrix_bytes / replace_s / replace_provenance

Each implicit row is measured in a subprocess (hidden ``--implicit-case``
mode) because ``ru_maxrss`` is a process-lifetime high-water mark — see
``tools/peak_rss.py``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import backend as core_backend
from repro.core.comm_graph import CommGraph
from repro.core.engine import PlacementEngine, PlacementRequest
from repro.core.topology import TorusTopology
from repro.workloads.patterns import npb_dt_like


def run(csv=print, backend: str = "numpy") -> dict:
    with core_backend.use(backend):
        return _run(csv=csv)


def _run(csv=print) -> dict:
    engine = PlacementEngine()
    out = {}
    for dims, n in [((4, 4, 4), 48), ((8, 8, 8), 85), ((8, 8, 8), 256),
                    ((16, 16), 192), ((8, 8, 8), 410)]:
        topo = TorusTopology(dims)
        wl = npb_dt_like(n, seed=3)
        req = PlacementRequest(comm=wl.comm, topology=topo)
        name = "x".join(map(str, dims))
        row = {}
        for pol in ("linear", "random", "greedy", "topo"):
            t0 = time.perf_counter()
            plan = engine.place(req, policy=pol,
                                rng=np.random.default_rng(0))
            dt = time.perf_counter() - t0
            row[pol] = (plan.hop_bytes, dt)
            csv(f"mapping_scale,{name}_n{n},{pol},{dt*1e3:.1f},"
                f"ms_place_time,hop_bytes={plan.hop_bytes:.3e}")
        out[f"{name}_n{n}"] = row
        rel = row["topo"][0] / row["linear"][0]
        csv(f"mapping_scale,{name}_n{n},topo_vs_linear_hopbytes,"
            f"{rel:.3f},ratio")

    out["cache"] = _cache_ablation(csv)
    return out


def _cache_ablation(csv=print, dims=(8, 8, 4), n=85, n_faulty=12,
                    repeats=3) -> dict:
    """Engine-cached vs uncached fault-aware placement latency.

    Uncached = a fresh engine per call (the pre-engine behaviour: every
    call site re-derived hop and Eq. 1 weight matrices).  Cached = one
    engine, matrices derived on the first call only.
    """
    topo = TorusTopology(dims)
    wl = npb_dt_like(n, seed=3)
    p_f = np.zeros(topo.n_nodes)
    p_f[np.random.default_rng(7).choice(topo.n_nodes, n_faulty,
                                        replace=False)] = 0.02
    req = PlacementRequest(comm=wl.comm, topology=topo, p_f=p_f)
    name = "x".join(map(str, dims))

    uncached = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        PlacementEngine().place(req, policy="tofa",
                                rng=np.random.default_rng(0))
        uncached.append(time.perf_counter() - t0)

    engine = PlacementEngine()
    engine.place(req, policy="tofa", rng=np.random.default_rng(0))  # warm
    cached = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        engine.place(req, policy="tofa", rng=np.random.default_rng(0))
        cached.append(time.perf_counter() - t0)

    dt_un, dt_c = float(np.median(uncached)), float(np.median(cached))
    speedup = dt_un / dt_c if dt_c > 0 else float("inf")
    csv(f"mapping_scale,cache_{name}_n{n},tofa_uncached,{dt_un*1e3:.1f},"
        f"ms_place_time")
    csv(f"mapping_scale,cache_{name}_n{n},tofa_cached,{dt_c*1e3:.1f},"
        f"ms_place_time")
    csv(f"mapping_scale,cache_{name}_n{n},cache_speedup,{speedup:.2f},x"
        f"  # hop/weight matrices reused across placements")
    return {"uncached_s": dt_un, "cached_s": dt_c, "speedup": speedup,
            "stats": engine.cache_stats()}


# ---------------------------------------------------------------------------
# Implicit-distance scaling (lazy metric, no dense N x N matrix)

# (case name, torus dims, n_procs, part of --fast smoke)
IMPLICIT_CASES = [
    ("torus-32x32x16/n1024/implicit", (32, 32, 16), 1024, True),
    ("torus-64x32x32/n2048/implicit", (64, 32, 32), 2048, False),
    ("torus-64x64x32/n2048/implicit", (64, 64, 32), 2048, False),
]
# smoke wall-clock budget for the 16k-node case (seconds, on the reference
# machine — scaled by the refine_scale calibration ratio at gate time).
# Measured: numpy warm ~7 s / cold ~8 s; x4 headroom.
IMPLICIT_WALL_BUDGET_S = 30.0
IMPLICIT_CALIBRATION_S = 0.009071  # refine_scale._calibrate() on the
#                                    machine the budget above was measured on
# optional second smoke leg: the 128k-node case runs only when its
# machine-normalised *predicted* wall fits the guard — slow CI runners
# skip the leg instead of timing out on it.
IMPLICIT_128K_CASE = ("torus-64x64x32/n2048/implicit", (64, 64, 32), 2048)
# measured on the reference machine: cold 25.1 s / warm 23.6 s / replace
# 57.1 s (exact Eq. 1 route walks under the 4-failure overlay), 2.0 GB
# peak RSS vs the 137 GB a dense matrix would take
IMPLICIT_128K_EST_S = 110.0       # reference-machine child wall (all phases)
IMPLICIT_128K_GUARD_S = 300.0     # run the leg only if est * scale fits this
IMPLICIT_128K_WALL_BUDGET_S = 95.0    # gate on the measured warm placement
N_REPLACE_FAILED = 4              # nodes killed by the replace micro-bench


def _ring_comm(n: int, w: float = 8.0) -> np.ndarray:
    G = np.zeros((n, n))
    i = np.arange(n)
    G[i, (i + 1) % n] = w
    G[(i + 1) % n, i] = w
    return G


def implicit_case_child(dims: tuple[int, ...], n: int,
                        backend: str = "numpy") -> dict:
    """Measure one implicit-torus placement in *this* process and return
    the row (run via subprocess so peak-RSS is per-case)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from tools.peak_rss import peak_rss_bytes

    topo = TorusTopology(dims)
    comm = CommGraph(n, G_v=_ring_comm(n))
    with core_backend.use(backend):
        engine = PlacementEngine()
        req = PlacementRequest(comm=comm, topology=topo)
        t0 = time.perf_counter()
        plan = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan = engine.place(req, policy="tofa", rng=np.random.default_rng(0))
        warm_s = time.perf_counter() - t0
        # fault-driven re-placement micro-bench: kill a handful of *used*
        # nodes and time the incremental move (exercises the lazy-exact
        # replace cost path — blocked row reductions, never a dense D)
        failed = np.random.default_rng(5).choice(
            np.asarray(plan.placement), size=N_REPLACE_FAILED, replace=False)
        t0 = time.perf_counter()
        plan_r = engine.replace(plan, failed_nodes=failed,
                                rng=np.random.default_rng(0))
        replace_s = time.perf_counter() - t0
    from repro.core.lazydist import is_lazy
    lazy = bool(is_lazy(engine.hops(topo)))
    name = f"torus-{'x'.join(map(str, dims))}/n{n}/implicit"
    return {
        "case": name,
        "topology": f"torus-{'x'.join(map(str, dims))}",
        "n_procs": n,
        "n_nodes": topo.n_nodes,
        "n_faulty": 0,
        "policy": "tofa",
        "cold_s": round(cold_s, 6),
        "warm_s": round(warm_s, 6),
        "hop_bytes": float(plan.hop_bytes),
        # additive keys (schema v1-compatible: absent on dense rows)
        "lazy": lazy,
        "backend": backend,
        "peak_rss_bytes": peak_rss_bytes(),
        "dense_matrix_bytes": topo.n_nodes * topo.n_nodes * 8,
        "replace_s": round(replace_s, 6),
        "replace_provenance": plan_r.provenance,
    }


def _measure_implicit(dims: tuple[int, ...], n: int, backend: str,
                      csv=print) -> dict:
    """Run one implicit case and report its row: in a subprocess (so
    peak RSS is per case), except on the jax backend, which runs it in
    this process — a chip serves one process, and the caller may already
    hold it.  In-process, ``peak_rss_bytes`` covers the whole run."""
    if backend == "jax":
        row = implicit_case_child(dims, n, backend=backend)
    else:
        repo = Path(__file__).resolve().parents[1]
        cmd = [sys.executable, "-m", "benchmarks.mapping_scale",
               "--implicit-case", "x".join(map(str, dims)), str(n),
               "--backend", backend]
        import os
        env = dict(os.environ)
        env["PYTHONPATH"] = str(repo / "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        out = subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                             text=True, check=True)
        row = json.loads(out.stdout.strip().splitlines()[-1])
    csv(f"mapping_scale,{row['case']},implicit,{row['warm_s']*1e3:.0f},"
        f"ms_place_time,cold={row['cold_s']:.2f}s,"
        f"replace={row['replace_s']*1e3:.0f}ms,"
        f"rss={row['peak_rss_bytes']/1e6:.0f}MB,"
        f"dense_would_be={row['dense_matrix_bytes']/1e9:.2f}GB,"
        f"lazy={row['lazy']},backend={row['backend']}")
    return row


def run_implicit(csv=print, backend: str = "numpy",
                 fast: bool = False) -> list[dict]:
    cases = [c for c in IMPLICIT_CASES if c[3]] if fast else IMPLICIT_CASES
    return [_measure_implicit(dims, n, backend, csv=csv)
            for _, dims, n, _ in cases]


def implicit_smoke(csv=print, backend: str = "numpy") -> int:
    """CI gate: the 16k-node implicit placement must stay lazy (peak RSS
    under the dense-matrix bytes alone) and inside the wall budget."""
    from benchmarks import refine_scale

    row = run_implicit(csv=csv, backend=backend, fast=True)[0]
    rc = 0
    if not row["lazy"]:
        csv("mapping_scale,implicit_smoke,FAIL,engine did not go lazy "
            f"(n_nodes={row['n_nodes']})")
        rc = 1
    # machine-speed normalisation, same yardstick as the refine gate
    scale = refine_scale._calibrate() / IMPLICIT_CALIBRATION_S
    scale = min(max(scale, 1.0 / refine_scale.CALIBRATION_CLAMP),
                refine_scale.CALIBRATION_CLAMP)
    limit = IMPLICIT_WALL_BUDGET_S * scale
    csv(f"mapping_scale,implicit_smoke,warm_s,{row['warm_s']:.2f},s,"
        f"machine_scale={scale:.2f},limit={limit:.1f}")
    if row["warm_s"] > limit:
        csv(f"mapping_scale,implicit_smoke,FAIL,warm {row['warm_s']:.1f}s "
            f"> machine-normalised budget {limit:.1f}s")
        rc = 1
    if row["peak_rss_bytes"] >= row["dense_matrix_bytes"]:
        csv(f"mapping_scale,implicit_smoke,FAIL,peak RSS "
            f"{row['peak_rss_bytes']/1e6:.0f}MB >= dense-matrix bytes "
            f"{row['dense_matrix_bytes']/1e6:.0f}MB — lazy path densified?")
        rc = 1
    else:
        csv(f"mapping_scale,implicit_smoke,rss_headroom,"
            f"{row['dense_matrix_bytes']/max(row['peak_rss_bytes'],1):.1f},x,"
            f"dense-matrix bytes / peak RSS")
    # 128k-node leg, behind the wall-budget guard: run it only when the
    # machine-normalised prediction fits — slow runners skip, not time out
    est = IMPLICIT_128K_EST_S * scale
    if est > IMPLICIT_128K_GUARD_S:
        csv(f"mapping_scale,implicit_smoke_128k,SKIP,predicted {est:.0f}s "
            f"> guard {IMPLICIT_128K_GUARD_S:.0f}s on this machine")
    else:
        _, dims, n = IMPLICIT_128K_CASE
        row = _measure_implicit(dims, n, backend, csv=csv)
        limit = IMPLICIT_128K_WALL_BUDGET_S * scale
        if not row["lazy"] or row["peak_rss_bytes"] >= row["dense_matrix_bytes"]:
            csv(f"mapping_scale,implicit_smoke_128k,FAIL,lazy={row['lazy']},"
                f"rss={row['peak_rss_bytes']/1e6:.0f}MB vs dense "
                f"{row['dense_matrix_bytes']/1e9:.0f}GB")
            rc = 1
        elif row["warm_s"] > limit:
            csv(f"mapping_scale,implicit_smoke_128k,FAIL,warm "
                f"{row['warm_s']:.1f}s > machine-normalised budget "
                f"{limit:.1f}s")
            rc = 1
        else:
            csv(f"mapping_scale,implicit_smoke_128k,PASS,"
                f"warm={row['warm_s']:.1f}s,replace={row['replace_s']:.2f}s,"
                f"rss={row['peak_rss_bytes']/1e6:.0f}MB")
    if rc == 0:
        csv("mapping_scale,implicit_smoke,PASS,lazy + within budgets")
    return rc


def scale_trajectory(csv=print, write: bool = False,
                     label: str | None = None,
                     backend: str = "numpy") -> dict:
    """Measure the refine_scale case matrix plus the implicit rows and
    (with ``write``) append one trajectory point to BENCH_mapping.json."""
    from benchmarks import refine_scale

    point = refine_scale.run(csv=csv, write=False, label=label)
    point["cases"].extend(run_implicit(csv=csv, backend=backend))
    if write:
        doc = refine_scale._load_baseline() or {
            "schema": refine_scale.SCHEMA_VERSION,
            "gate": {"case": refine_scale.GATE_CASE,
                     "factor": refine_scale.GATE_FACTOR},
            "trajectory": [],
        }
        doc["trajectory"].append(point)
        with open(refine_scale.BENCH_PATH, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        csv(f"mapping_scale,write,{refine_scale.BENCH_PATH.name},"
            f"trajectory_points={len(doc['trajectory'])}")
    return point


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy")
    ap.add_argument("--implicit", action="store_true",
                    help="measure implicit-distance (lazy) placements at "
                         "16k/64k nodes, one subprocess per row")
    ap.add_argument("--fast", action="store_true",
                    help="with --implicit: CI smoke — gate the 16k-node "
                         "case on wall-clock and peak-RSS budgets")
    ap.add_argument("--scale", action="store_true",
                    help="measure the BENCH_mapping trajectory matrix "
                         "(refine_scale cases + implicit rows)")
    ap.add_argument("--write", action="store_true",
                    help="with --scale: append the point to "
                         "BENCH_mapping.json")
    ap.add_argument("--label", default=None,
                    help="trajectory point label (e.g. the PR name)")
    ap.add_argument("--implicit-case", default=None, metavar="DIMS",
                    help=argparse.SUPPRESS)  # subprocess-only entry
    ap.add_argument("n_procs", nargs="?", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.implicit_case:
        dims = tuple(int(d) for d in args.implicit_case.split("x"))
        row = implicit_case_child(dims, int(args.n_procs or 1024),
                                  backend=args.backend)
        print(json.dumps(row))
        return 0
    if args.implicit:
        if args.fast:
            return implicit_smoke(backend=args.backend)
        run_implicit(backend=args.backend)
        return 0
    if args.scale:
        scale_trajectory(write=args.write, label=args.label,
                         backend=args.backend)
        return 0
    run(backend=args.backend)
    return 0


if __name__ == "__main__":
    sys.exit(main())
