"""Chip benchmark of the placement system: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  The cell is looked up in
``BENCHMARK.json``; everything else is found by name under the
benchmark's own directories (``paths``):

* ``configs/<config>.json``  the deployment (fabric, source, guarantees);
* ``fabrics/<kind>.py``      the fabric kind's reference Eq. (1) weights
                             and the program's topology for it;
* ``traffic/<traffic>.json`` the mix's parameters and its driver;
* ``drivers/<driver>.py``    the loop that offers the mix to the program,
                             and the check of what it produced;
* ``metrics/<metric>.py``    one reader per metric, ``read(rec)``;
* ``limits/<cell>.json``     the limits of the correctness check;
* ``peaks.json``             the chip's published peaks, by device kind.

A new configuration, fabric kind, mix, driver or metric is new files
and entries, never an edit.  The run needs a TPU with at least the cell's chips, and
exits non-zero with no result without one.  The last line of standard
output is the result's JSON object; the numbers compared with the
reference, each beside its limit, end both standard error and the
result line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class RunError(RuntimeError):
    """The run cannot go ahead: no chip, or a cell that is not there."""


class Bench:
    """``BENCHMARK.json`` and the files it names, found by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise RunError(f"no BENCHMARK.json in {self.root}")
        self.spec = json.loads(path.read_text())
        self.dirs = [self.root / p for p in self.spec["paths"]]

    def find(self, *parts: str) -> Path:
        for d in self.dirs:
            path = d.joinpath(*parts)
            if path.is_file():
                return path
        raise RunError(f"{'/'.join(parts)} is in none of "
                       f"{self.spec['paths']}")

    def load_json(self, *parts: str) -> dict:
        return json.loads(self.find(*parts).read_text())

    def module(self, kind: str, name: str):
        path = self.find(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise RunError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise RunError(f"no config {name!r} in BENCHMARK.json")

    def metrics_of(self, cell: str, group: str) -> list[dict]:
        """The metrics of ``group`` (``end_to_end`` | ``per_layer``) that
        this cell reports."""
        return [m for m in self.spec[group]
                if "workloads" not in m or cell in m["workloads"]]


def _devices(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise RunError(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise RunError(f"{chips} chips asked for, {len(devices)} seen")
    return devices[:chips]


def _peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, require_chip: bool = True,
            precision: str = "float32") -> dict:
    """One run of one cell; returns the result object.  ``require_chip``
    and ``precision`` are for the benchmark's own tests and control."""
    t_begin = time.perf_counter()
    bench = Bench(root)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.load_json("traffic", cell["traffic"] + ".json")
    limits = bench.load_json("limits", workload + ".json")
    fabric = bench.module("fabrics", config["fabric"]["kind"])
    drv = bench.module("drivers", mix["driver"])
    if not (bench.root / "src" / "repro").is_dir():
        raise RunError(f"the program is not in {bench.root / 'src'}")
    os.environ["REPRO_JAX_DEVICES"] = str(cell["chips"])
    cache = bench.root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(bench.root / "src"))
    devices = _devices(cell["chips"], require_chip)
    peaks = None
    if require_chip:
        table = bench.load_json("peaks.json")
        kind = devices[0].device_kind
        if kind not in table:
            raise RunError(f"device kind {kind!r} is not in peaks.json")
        peaks = table[kind]

    from chipbench import check, program
    spans = program.Spans()
    driver = drv.Driver(fabric, config["fabric"], mix, seed, spans)
    driver.setup()
    compiles = program.CompileCounter()
    trace_dir = bench.root / ".chipbench" / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    setup_s = time.perf_counter() - t_begin
    compiles.active = True
    rec = driver.window(seconds, str(trace_dir) if trace else None)
    compiles.active = False
    spans.stop_trace()
    rec["setup_s"] = setup_s
    rec["programs_built_in_window"] = compiles.names
    rec["peaks"] = peaks
    memory_peak = _peak_memory(devices)
    answers = driver.answers()
    driver.close()
    del driver
    gc.collect()

    t_check = time.perf_counter()
    verdict = drv.check(fabric.Reference(config["fabric"]), answers,
                        precision)
    rec["quality"] = verdict["quality"]
    rec["check_s"] = time.perf_counter() - t_check

    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from chipbench import trace as tr
        modules, op_time, host_spans = tr.load(trace_dir,
                                               [d.id for d in devices])
        rec["trace"] = tr.reduce(modules, host_spans,
                                 tr.window_of(host_spans))
        rec["trace"]["op_time_s"] = op_time
        result_device["busy_s"] = rec["trace"]["busy_s"]
        result_device["window_s"] = rec["trace"]["window_s"]
        breakdown = {"device_ops": rec["trace"]["device_ops"],
                     "idle_gaps": rec["trace"]["idle_gaps"]}
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics_of(workload, group):
        value = bench.module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    numbers = verdict["numbers"]
    out = {"correct": check.verdict(numbers, limits),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    untraced = {}
    if not trace:
        for m in bench.metrics_of(workload, "per_layer"):
            if m["source"] != "device_trace":
                untraced[m["name"]] = bench.module("metrics",
                                                   m["name"]).read(rec)
    out["notes"] = {"programs_built_in_window":
                    rec["programs_built_in_window"],
                    "window_s": rec["window_s"], "check_s": rec["check_s"],
                    "per_layer_untraced": untraced,
                    "why_not_correct": verdict["why"],
                    **rec.get("notes", {})}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        out = execute(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except RunError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
