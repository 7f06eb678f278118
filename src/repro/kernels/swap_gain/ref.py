"""Swap-gain oracle — the dense gains row of the pairwise-swap refiner.

For mover ``i`` over a placement with gathered pairwise distances ``M``
and guest weights ``G`` (``contrib = (G * M).sum(1)``), the gain of
swapping ``i`` with every other process ``j`` is

    gains = contrib[i] + contrib - 2 * G[i] * M[i] - M @ G[i] - G @ M[i]

(the i<->j mutual term cancels because swapping endpoints preserves
their own distance).  This is the jitted-JAX fallback the Pallas kernel
is differentially tested against, and the dense-guest path of
:mod:`repro.core.mapping_jax` routes through it off-TPU.
"""
from __future__ import annotations

import jax.numpy as jnp


def swap_gain_ref(M, G, contrib, i):
    """(n, n), (n, n), (n,), scalar index -> (n,) gains row."""
    Mi, Gi = M[i], G[i]
    return (contrib[i] + contrib - 2.0 * Gi * Mi
            - M @ Gi - G @ Mi)


# swap acceptance threshold of the device refine: read here, by the
# Pallas kernel and by repro.core.mapping_jax._refine_one; the NumPy
# loops read repro.core.mapping.GAIN_EPS, which holds the same value
GAIN_EPS = 1e-9


def swap_select_ref(M, G, contrib, i, n_valid):
    """Fused select step of the refiner: gains row + masked argmax +
    the apply decision, in one traced expression.

    Returns ``(gain, j)``: the best masked gain and the swap partner.
    Masking matches the refine loop exactly — ``gains[i] = 0`` (the
    identity swap), indices ``>= n_valid`` are ``-inf`` padding — and the
    argmax keeps the *first* occurrence on ties.  The accept test and
    identity-swap substitution happen here too: when the best gain does
    not clear ``GAIN_EPS`` (or mover ``i`` is itself padding),
    ``j == i`` so the caller applies the returned swap unconditionally.
    """
    g = swap_gain_ref(M, G, contrib, i)
    n = g.shape[0]
    g = g.at[i].set(0.0)
    g = jnp.where(jnp.arange(n) < n_valid, g, -jnp.inf)
    j_raw = jnp.argmax(g)
    gain = g[j_raw]
    j = jnp.where((gain > GAIN_EPS) & (i < n_valid), j_raw, i)
    return gain, j.astype(jnp.int32)
