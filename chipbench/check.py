"""Whether what the window produced is correct, by the plain reference.

Each driver (``drivers/<kind>.py``) has a ``check(fabric, answers,
precision)`` that reduces what its window produced to the numbers below,
with the helpers of this module; each number is compared against the
cell's limit in ``limits/<cell>.json``:

``invalid``    placements (and refined candidates) that break the
               deployment's guarantees: a rank without its own node, a
               node that is not allocatable, a refine that returns other
               nodes than it was given, a placement that is none of the
               refined candidates.  Exact: limit 0.
``claim_err``  the widest relative gap between the hop-bytes a plan
               states (``hop_bytes_fault_weighted``, which the program
               computes on the device) and the reference's float64
               hop-bytes of the same placement under the same health.
``swap_gap``   over a seeded sample of the window's device refines, the
               largest share of a refined candidate's hop-bytes that one
               swap of two ranks would still save, by the reference.

``precision="bfloat16"`` is the control: the reference, computed in
bfloat16 with float32 sums on the device, takes the program's place in
stating each plan's hop-bytes.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference


def bf16_hop_bytes(G: np.ndarray, M: np.ndarray) -> float:
    import jax.numpy as jnp
    g = jnp.asarray(G, dtype=jnp.bfloat16)
    m = jnp.asarray(M, dtype=jnp.bfloat16)
    return 0.5 * float(jnp.sum(g * m, dtype=jnp.float32))


def produced(ans: dict, tag, nodes) -> bool:
    """Whether ``nodes`` is a row some device refine of ``tag`` returned:
    a tofa placement is always one of its refined candidates, so any
    other answer was altered after the refine."""
    rows = ans["produced"].get(tag, [])
    return any((out == nodes).all(axis=1).any() for out in rows
               if out.shape[1] == len(nodes))


def sample_gap(fabric, rec: dict, penal: np.ndarray) -> tuple[float, int]:
    """(largest swap gap among the refined rows, rows that are not a
    permutation of their input's nodes)."""
    worst, bad = 0.0, 0
    G = np.asarray(rec["G"], dtype=np.float64)
    for src, dst in zip(rec["inp"], rec["out"]):
        if not np.array_equal(np.sort(src), np.sort(dst)):
            bad += 1
            continue
        worst = max(worst, reference.swap_gap(G, fabric, dst, penal))
    return worst, bad


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number is at or under its limit."""
    return all(numbers[k] <= limits[k] for k in limits)
