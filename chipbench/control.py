"""Readings of a cell's control and planted faults, for setting its limits.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13 \\
        --seconds 20 [--faults refine_unchanged ...]

For each seed it runs the cell as the benchmark does (one process, so
later runs reuse compiled programs) three ways and prints one JSON line
each with the numbers the check compares:

* ``program``  the program as it is;
* ``control``  the reference in bfloat16 (float32 sums) taking the
               program's place in stating each plan's hop-bytes;
* each fault of ``chipbench/faults.py`` named in ``--faults``.

The lower reading of a limit is the largest ``program`` reading over a
dozen seeds or more; the upper is the smallest ``control`` (or fault)
reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--no-program", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import faults, run

    def one(seed, how, precision="float32", fault=None):
        if fault is None:
            out = run.execute(ROOT, args.workload, seed, args.seconds, False,
                              precision=precision)
        else:
            with faults.CLOSED_LOOP[fault]():
                out = run.execute(ROOT, args.workload, seed, args.seconds,
                                  False)
        row = {"seed": seed, "how": how, "correct": out["correct"],
               "attempted": out["attempted"],
               **{k: v["value"] for k, v in out["checks"].items()}}
        print(json.dumps(row), flush=True)

    for seed in args.seeds:
        if not args.no_program:
            one(seed, "program")
        one(seed, "control", precision="bfloat16")
        for fault in args.faults:
            one(seed, fault, fault=fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
