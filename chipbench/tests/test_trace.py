"""The reduction from a device trace to busy time, idle gaps and kernel
time, on synthetic events."""
import importlib.util
from pathlib import Path

import pytest

from chipbench import trace as tr

E = tr.Event


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]


def test_gaps_are_the_complement_within_the_window():
    busy = tr.union([(2, 4), (6, 7)])
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], 3, 5) == [(3, 5)]


def test_label_takes_the_innermost_span():
    spans = [E("chipbench.window", 0, 100), E("chipbench.place", 10, 50),
             E("chipbench.refine", 20, 10)]
    assert tr.label([5, 15, 25, 70, 150], spans) == [
        "window", "place", "refine", "window", "none"]


def test_reduce_busy_idle_and_top_programs():
    modules = {0: [E("a", 10, 20), E("b", 25, 10), E("a", 60, 10),
                   E("late", 95, 20)]}
    spans = [E("chipbench.window", 0, 100), E("chipbench.place", 0, 50),
             E("chipbench.refine", 40, 20)]
    out = tr.reduce(modules, spans, (0, 100))
    # busy: [10, 35] + [60, 70] + [95, 100] (clipped) = 40 ns
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    idle = dict(out["idle_gaps"])
    # gaps [0,10] place, [35,60] place (starts at 35 in place), [70,95]
    assert idle["place"] == pytest.approx(35e-9)
    assert idle["window"] == pytest.approx(25e-9)
    assert out["device_ops"][0][0] == "a"
    assert out["device_ops"][0][1] == pytest.approx(30e-9)


def test_reduce_averages_over_devices():
    modules = {0: [E("a", 0, 50)], 1: [E("a", 0, 100)]}
    out = tr.reduce(modules, [E("chipbench.window", 0, 100)], (0, 100))
    assert out["busy_s"] == pytest.approx(75e-9)


def _metric(name):
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KERNEL = ('%vmap__.1 = f32[4,128]{1,0:T(4,128)S(1)} custom-call(f32[3,4]'
          '{1,0:T(4,128)S(1)} %bitcast.393, f32[3,128]{1,0:T(4,128)S(1)} '
          '%pad_bitcast_fusion.8), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={f32[3,4]{1,0}, f32[3,128]{1,0}}')
OTHER = ('%fusion.13 = f32[4,3]{1,0:T(4,128)S(1)} fusion(f32[27648,3]'
         '{1,0:T(8,128)S(1)} %copy.20), kind=kCustom')


def test_kernel_time_matches_only_the_hop_kernel():
    m = _metric("fattree_hop_roofline")
    assert m.kernel_time({KERNEL: 2e-6, OTHER: 5.0}) == pytest.approx(2e-6)


def test_roofline_share_from_shapes_and_time():
    m = _metric("fattree_hop_roofline")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    shapes = [(2, 512), (1, 4)]
    least, bound = m.least_time(shapes, peaks)
    nbytes = 4 * (2 * 512 * 512 + 6 * 2 * 512) + 4 * (16 + 6 * 4)
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)
    rec = {"trace": {"op_time_s": {KERNEL: 4 * least}}, "peaks": peaks,
           "traced_refine_shapes": shapes}
    assert m.read(rec) == pytest.approx(25.0)
    # nothing to read: no kernel in the trace
    rec["trace"]["op_time_s"] = {OTHER: 1.0}
    assert m.read(rec) is None
