"""swap_select — jit'd public wrapper with backend dispatch."""
from __future__ import annotations

import functools

import jax

from repro.kernels.swap_gain.ref import swap_select_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def swap_select(M, G, contrib, i, n_valid, *, impl: str = "auto"):
    """Fused select step of the dense refiner: gains row, masked argmax
    and the accept-or-identity apply decision in one kernel.

    Returns ``(gain, j)`` scalars; ``j == i`` encodes a rejected mover
    (the identity-swap convention of ``mapping_jax._refine_one``), so the
    refine loop applies the returned swap unconditionally and never
    materialises a gains row.  ``impl="auto"`` runs the Pallas kernel on
    TPU and the jitted reference everywhere else.  Decision-identical to
    composing :func:`.ref.swap_gain_ref` with the loop's own
    mask/argmax/threshold — the Pallas kernel and the jitted reference
    share the arithmetic and the first-occurrence tie-break
    (differentially tested in ``tests/test_kernels.py``).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.swap_gain.kernel import swap_select_tpu
        return swap_select_tpu(M, G, contrib, i, n_valid,
                               interpret=(impl == "pallas_interpret"))
    return swap_select_ref(M, G, contrib, i, n_valid)
