"""Share of the fat-tree hop-distance kernel's roofline, in percent.

The least time of each call is the larger of its operations over the
chip's peak and its bytes over the chip's memory bandwidth; the share is
the least time summed over the calls in the traced part of the window
over the kernel's time in the device trace.  The peak is the published
bfloat16 one, so for this float32 kernel the share is understated,
never overstated.

A call is one device refine on an implicit fat-tree: it builds the
(n, n) distance block of each of its B candidates from (n, 3)
coordinates, n the ranks refined (without the program's padding).  Per
element: three compares, two ands, three scalings and three
subtractions; bytes: the two coordinate tables read (float32) and the
block written (float32).
"""
import re

# the kernel in the device trace: a Pallas call (``tpu_custom_call``)
# that reads two (3, m) float32 coordinate tables (with a leading batch
# dimension when a stack is refined), as the refine's
# vmapped fattree_hop appears on a TPU v5e (XLA names it ``%vmap__.N``)
PATTERNS = [re.compile(r'custom-call\(f32\[(\d+,)?3,\d+\]\{[^}]*\} %\S+, '
                       r'f32\[(\d+,)?3,\d+\]\{[^}]*\} %\S+\), '
                       r'custom_call_target="tpu_custom_call"')]
OPS_PER_ELEMENT = 11
BYTES_PER_ELEMENT = 4


def cost(B: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one call on a stack of ``B`` candidates of
    ``n`` ranks."""
    elems = B * n * n
    return (OPS_PER_ELEMENT * elems,
            BYTES_PER_ELEMENT * (elems + 2 * 3 * B * n))


def least_time(shapes, peaks) -> tuple[float, str]:
    """Summed least time of the calls, and which side bounds most of
    it."""
    t_ops = t_mem = total = 0.0
    for B, n in shapes:
        ops, nbytes = cost(B, n)
        a = ops / peaks["bf16_flops_per_s"]
        b = nbytes / peaks["hbm_bytes_per_s"]
        total += max(a, b)
        t_ops, t_mem = t_ops + a, t_mem + b
    return total, ("compute" if t_ops > t_mem else "memory")


def kernel_time(op_time_s: dict) -> float:
    return sum(t for name, t in op_time_s.items()
               if any(p.search(name) for p in PATTERNS))


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("peaks") or not rec.get("traced_refine_shapes"):
        return None
    spent = kernel_time(t["op_time_s"])
    if spent <= 0:
        return None
    least, _bound = least_time(rec["traced_refine_shapes"], rec["peaks"])
    return 100.0 * least / spent
