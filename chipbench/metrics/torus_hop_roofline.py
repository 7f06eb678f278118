"""Share of the penalised torus hop-distance kernel's roofline, in
percent.

The least time of each call is the larger of its operations over the
chip's peak and its bytes over the chip's memory bandwidth; the share is
the least time summed over the calls in the traced part of the window
over the kernel's time in the device trace.  The peak is the published
bfloat16 one, so for this float32 kernel the share is understated,
never overstated.

A call is one device refine on an implicit torus of ``ndim`` dimensions
with ``F`` penalised nodes in its table (padded to a power of two): it
builds the (n, n) Eq. (1) block ``hops + 100 * badlinks`` of each of its
B candidates from (n, ndim) coordinates, n the ranks refined (without
the program's padding).  ``ndim`` and ``F`` are read from the operand
shapes of the matched operations in the trace: the coordinate blocks
(B, ndim, n) and the penalised table (ndim, F); a healthy torus's kernel
has no table (F = 0).  Operations per element, as the kernel's body
states them: five per dimension for the hops (difference, absolute
value, wrap, minimum, sum); with F > 0, ten per dimension for the
route's direction and reach, two to add the term, and per penalised
node nine per dimension (on-route test and next-link test) and four to
count its links.  Bytes: the two coordinate tables read and the block
written (float32), and the two small tables.
"""
import re

# the kernel in the device trace: the Pallas call named ``torus_hop``
# (``tpu_custom_call``; XLA names the refine's vmapped call
# ``%vmap_torus_hop_.N``)
PATTERN = re.compile(r'torus_hop[^ =]* = .*custom-call\(.*'
                     r'custom_call_target="tpu_custom_call"')
OPERANDS = re.compile(r'custom-call\((.*?)\), custom_call_target')
SHAPE = re.compile(r'f32\[([\d,]*)\]')
BYTES_PER_ELEMENT = 4


def ops_per_element(ndim: int, F: int) -> int:
    if F == 0:
        return 5 * ndim
    return 5 * ndim + 10 * ndim + 2 + F * (9 * ndim + 4)


def cost(B: int, n: int, ndim: int, F: int) -> tuple[float, float]:
    """(operations, bytes) of one call on a stack of ``B`` candidates of
    ``n`` ranks."""
    elems = B * n * n
    return (ops_per_element(ndim, F) * elems,
            BYTES_PER_ELEMENT * (elems + 2 * ndim * B * n + 3 * ndim * F))


def operand_shapes(name: str) -> list[tuple[int, ...]]:
    m = OPERANDS.search(name)
    if m is None:
        return []
    return [tuple(int(x) for x in s.split(",") if x)
            for s in SHAPE.findall(m.group(1))]


def table_shape(op_time_s: dict) -> tuple[int, int] | None:
    """(ndim, F) of the matched operations, the least F where they
    differ (so the share is never overstated), or None."""
    found = []
    for name in op_time_s:
        if not PATTERN.search(name):
            continue
        shapes = operand_shapes(name)
        if len(shapes) < 2 or len(shapes[0]) < 2:
            continue
        ndim = shapes[0][-2]
        F = shapes[2][-1] if len(shapes) >= 4 else 0
        found.append((F, ndim))
    if not found:
        return None
    F, ndim = min(found)
    return ndim, F


def least_time(shapes, ndim: int, F: int, peaks) -> tuple[float, str]:
    """Summed least time of the calls, and which side bounds most of
    it."""
    t_ops = t_mem = total = 0.0
    for B, n in shapes:
        ops, nbytes = cost(B, n, ndim, F)
        a = ops / peaks["bf16_flops_per_s"]
        b = nbytes / peaks["hbm_bytes_per_s"]
        total += max(a, b)
        t_ops, t_mem = t_ops + a, t_mem + b
    return total, ("compute" if t_ops > t_mem else "memory")


def kernel_time(op_time_s: dict) -> float:
    return sum(t for name, t in op_time_s.items() if PATTERN.search(name))


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("peaks") or not rec.get("traced_refine_shapes"):
        return None
    table = table_shape(t["op_time_s"])
    spent = kernel_time(t["op_time_s"])
    if table is None or spent <= 0:
        return None
    least, _bound = least_time(rec["traced_refine_shapes"], *table,
                               rec["peaks"])
    return 100.0 * least / spent
