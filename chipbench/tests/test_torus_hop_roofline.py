"""The penalised torus kernel's roofline share, from synthetic trace
operations: the kernel is found by its Pallas name, and the table's
width F and the torus's dimensions by its operand shapes."""
import importlib.util
from pathlib import Path

import pytest

# as the refine's vmapped Pallas call reads in a TPU v5e trace: two
# batched coordinate blocks, the (ndim, F) table and its neighbour table
KERNEL = ('%vmap_torus_hop_.1 = f32[2,512,512]{2,1,0:T(8,128)S(1)} '
          'custom-call(f32[2,4,512]{2,1,0:T(4,128)S(1)} %bitcast.503, '
          'f32[2,4,512]{2,1,0:T(4,128)S(1)} %bitcast.504, '
          'f32[4,16]{1,0:T(4,128)S(6)} %copy-done, '
          'f32[8,16]{1,0:T(8,128)S(6)} %convert_element_type.117), '
          'custom_call_target="tpu_custom_call"')
HEALTHY = ('%vmap_torus_hop_.2 = f32[1,512,512]{2,1,0:T(8,128)S(1)} '
           'custom-call(f32[1,3,512]{2,1,0} %bitcast.1, '
           'f32[1,3,512]{2,1,0} %bitcast.2), '
           'custom_call_target="tpu_custom_call"')
FATTREE = ('%vmap__.1 = f32[4,128]{1,0:T(4,128)S(1)} custom-call(f32[3,4]'
           '{1,0:T(4,128)S(1)} %bitcast.393, f32[3,128]{1,0:T(4,128)S(1)} '
           '%pad_bitcast_fusion.8), custom_call_target="tpu_custom_call"')
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _metric():
    path = (Path(__file__).resolve().parents[1] / "metrics"
            / "torus_hop_roofline.py")
    spec = importlib.util.spec_from_file_location("torus_hop_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_found_by_name_and_table_by_shapes():
    m = _metric()
    ops = {KERNEL: 3e-6, FATTREE: 5.0}
    assert m.kernel_time(ops) == pytest.approx(3e-6)
    assert m.table_shape(ops) == (4, 16)
    assert m.table_shape({HEALTHY: 1.0}) == (3, 0)
    assert m.table_shape({FATTREE: 1.0}) is None


def test_share_from_shapes_and_time():
    m = _metric()
    shapes = [(2, 512), (1, 64)]
    least, bound = m.least_time(shapes, 4, 16, PEAKS)
    per_elem = 5 * 4 + 10 * 4 + 2 + 16 * (9 * 4 + 4)
    want = (max(per_elem * 2 * 512 * 512 / PEAKS["bf16_flops_per_s"],
                4 * (2 * 512 * 512 + 2 * 4 * 2 * 512 + 3 * 4 * 16)
                / PEAKS["hbm_bytes_per_s"])
            + max(per_elem * 64 * 64 / PEAKS["bf16_flops_per_s"],
                  4 * (64 * 64 + 2 * 4 * 64 + 3 * 4 * 16)
                  / PEAKS["hbm_bytes_per_s"]))
    assert least == pytest.approx(want)
    assert bound == "memory"
    rec = {"trace": {"op_time_s": {KERNEL: 4 * least}}, "peaks": PEAKS,
           "traced_refine_shapes": shapes}
    assert m.read(rec) == pytest.approx(25.0)
    # nothing to read: no penalised torus kernel in the trace
    rec["trace"]["op_time_s"] = {FATTREE: 1.0}
    assert m.read(rec) is None
