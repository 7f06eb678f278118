"""The harness end to end at tiny sizes on the CPU: discovery by name,
the refusal to run without a chip, and the check seeing the control and
every planted fault."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import faults, run

from .conftest import REPO

SEED = 2 ** 33 + 17


def _execute(root, cell, seconds=4.0, **kw):
    return run.execute(root, cell, SEED, seconds, False, require_chip=False,
                       **kw)


def test_new_config_fabric_mix_and_metric_are_found_from_files_alone(
        tmp_path, tiny_root):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root, symlinks=True)
    cb = root / "chipbench"
    # a fabric kind of its own: a file found by the kind's name
    shutil.copy(cb / "fabrics/torus.py", cb / "fabrics/ring.py")
    (cb / "configs/ring-torus.json").write_text(
        json.dumps({"fabric": {"kind": "ring", "dims": [16, 4]}}))
    mix = json.loads((cb / "traffic/tiny-batch.json").read_text())
    mix["jobs"] = [{"guest": "lammps", "ranks": 8, "faulty": 3, "p_f": 0.02}]
    (cb / "traffic/one-kind.json").write_text(json.dumps(mix))
    (cb / "metrics/placements_total.py").write_text(
        '"""Placements in the window."""\n\n\n'
        'def read(rec):\n    return float(rec["placements"])\n')
    shutil.copy(cb / "limits/tiny-torus.batch.json",
                cb / "limits/ring-torus.one-kind.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ring-torus", "source": "a test",
                             "file": "chipbench/configs/ring-torus.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "ring-torus.one-kind",
                               "config": "ring-torus",
                               "traffic": "one-kind", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"].append({"name": "placements_total", "unit": "1",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["ring-torus.one-kind"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _execute(root, "ring-torus.one-kind")
    assert out["correct"], out
    assert out["metrics"]["placements_total"]["value"] >= 1
    assert "placements_per_s" in out["metrics"]
    assert "place_p75_ms" not in out["metrics"]
    assert list(out)[-1] == "checks"


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "torus-8x8x8.paper-batch", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "torus-8x8x8.paper-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["tiny-torus.batch", "tiny-fattree.large"])
def test_sound_runs_are_correct(tiny_root, cell):
    out = _execute(tiny_root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["notes"]["health_states"] == out["attempted"]


@pytest.mark.parametrize("cell", ["tiny-torus.batch", "tiny-fattree.large"])
def test_a_traced_run_traces_whole_placements(tiny_root, cell):
    from chipbench import trace as tr
    out = run.execute(tiny_root, cell, SEED, 4.0, True, require_chip=False)
    assert out["correct"], out["checks"]
    bench = run.Bench(tiny_root)
    mix = bench.load_json("traffic", bench.cell(cell)["traffic"] + ".json")
    k = mix["trace_placements"]
    assert out["notes"]["traced_placements"] == k
    _, _, spans = tr.load(tiny_root / ".chipbench" / "trace" / cell, [])
    lo, hi = tr.window_of(spans)
    places = [ev for ev in spans if ev.name == "chipbench.place"]
    assert len(places) == k
    assert all(lo <= ev.start_ns and ev.end_ns <= hi for ev in places)
    assert out["device"]["window_s"] == pytest.approx((hi - lo) * 1e-9)


def _driver(root, cell, seed):
    bench = run.Bench(root)
    c = bench.cell(cell)
    mix = bench.load_json("traffic", c["traffic"] + ".json")
    spec = bench.config(c["config"])["fabric"]
    drv = bench.module("drivers", mix["driver"]).Driver(
        bench.module("fabrics", spec["kind"]), spec, mix, seed, None)
    drv.n_nodes = drv.fabric.Reference(spec).n_nodes
    return drv, mix["shuffle_block"]


@pytest.mark.parametrize("cell", ["torus-8x8x8.paper-batch",
                                  "fattree-k48.large-jobs"])
def test_every_seed_draws_the_same_health_states_in_another_order(cell):
    a, block = _driver(REPO, cell, 3)
    b, _ = _driver(REPO, cell, 2 ** 33 + 5)
    turns = 16 * block
    for k in range(len(a.kinds)):
        da = [a.draw(k, t) for t in range(turns)]
        db = [b.draw(k, t) for t in range(turns)]
        assert sorted(j for j, _ in da) == list(range(turns))
        assert [j for j, _ in da] != [j for j, _ in db]
        for t in range(0, turns, block):
            assert ({j for j, _ in da[t:t + block]}
                    == {j for j, _ in db[t:t + block]})
        by_j = {j: p for j, p in db}
        for j, p in da:
            np.testing.assert_array_equal(p, by_j[j])
        states = {np.flatnonzero(p).tobytes() for _, p in da}
        assert len(states) == turns


def test_an_unknown_fabric_kind_is_refused_by_name(tmp_path, tiny_root):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root, symlinks=True)
    (root / "chipbench/configs/tiny-torus.json").write_text(
        json.dumps({"fabric": {"kind": "dragonfly", "groups": 9}}))
    with pytest.raises(run.RunError, match="fabrics/dragonfly.py"):
        _execute(root, "tiny-torus.batch")


@pytest.mark.parametrize("cell", ["tiny-torus.batch", "tiny-fattree.large"])
def test_the_control_is_not_correct(tiny_root, cell):
    out = _execute(tiny_root, cell, precision="bfloat16")
    assert not out["correct"]
    c = out["checks"]["claim_err"]
    assert c["value"] > c["limit"]


CASES = [(c, f) for c in ("tiny-torus.batch", "tiny-fattree.large")
         for f in faults.CLOSED_LOOP]


@pytest.mark.parametrize("cell,fault", CASES)
def test_a_broken_timed_path_is_not_correct(tiny_root, cell, fault):
    with faults.CLOSED_LOOP[fault]():
        out = _execute(tiny_root, cell)
    assert not out["correct"], (fault, out["checks"])
