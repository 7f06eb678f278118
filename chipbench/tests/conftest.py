"""Fixtures of the benchmark's own tests: a checkout with tiny cells.

These tests are not part of the repository's tier-1 run (its
``testpaths`` is ``tests/``); run them by hand from the repository root:

    python -m pytest chipbench/tests -q
"""
import json
import os
import shutil
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = Path(__file__).resolve().parents[2]

# tiny stand-ins of the real cells, with the real cells' limits
TINY = {
    "tiny-torus.batch": ("tiny-torus", "tiny-batch",
                         "torus-8x8x8.paper-batch"),
    "tiny-fattree.large": ("tiny-fattree", "tiny-large",
                           "fattree-k48.large-jobs"),
}


def _tiny_mixes(cb: Path) -> None:
    pb = json.loads((cb / "traffic/paper-batch.json").read_text())
    pb["jobs"] = [{"guest": "npb_dt", "ranks": 21, "faulty": 4, "p_f": 0.02},
                  {"guest": "lammps", "ranks": 16, "faulty": 4, "p_f": 0.02}]
    (cb / "traffic/tiny-batch.json").write_text(json.dumps(pb))
    lj = json.loads((cb / "traffic/large-jobs.json").read_text())
    lj["jobs"] = [{"guest": "npb_dt", "ranks": 64, "faulty": 4, "p_f": 0.02},
                  {"guest": "lammps", "ranks": 64, "faulty": 4, "p_f": 0.02}]
    lj["warm"]["select"] = [96, 100]
    (cb / "traffic/tiny-large.json").write_text(json.dumps(lj))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout holding the benchmark, the program (linked) and a
    ``BENCHMARK.json`` with the real cells plus tiny ones."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", root / "src")
    cb = root / "chipbench"
    (cb / "configs/tiny-torus.json").write_text(
        json.dumps({"fabric": {"kind": "torus", "dims": [4, 4, 4]}}))
    (cb / "configs/tiny-fattree.json").write_text(
        json.dumps({"fabric": {"kind": "fattree", "k": 8}}))
    _tiny_mixes(cb)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("tiny-torus", "tiny-fattree"):
        bench["configs"].append({
            "name": name, "source": "a test size",
            "file": f"chipbench/configs/{name}.json", "reduced": [],
            "why": "test size"})
    for cell, (config, traffic, real) in TINY.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test size"})
        shutil.copy(cb / f"limits/{real}.json", cb / f"limits/{cell}.json")
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture(autouse=True)
def _lazy_fattree(monkeypatch):
    # the k=8 fat-tree (128 hosts) takes the implicit, multilevel path
    # that the k=48 one takes above the engine's default threshold
    monkeypatch.setenv("REPRO_LAZY_THRESHOLD", "64")
