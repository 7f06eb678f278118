"""Implicit torus hop distance — jitted-jnp reference.

Differential oracle for the Pallas kernel and the off-TPU fallback of
``impl="auto"`` dispatch in :mod:`repro.kernels.hop_dist.ops`.  The
per-dimension loop is unrolled at trace time (``dims`` is static), so no
(m, k, ndim) intermediate is ever materialised — peak memory is one
(m, k) block, times the penalised table's width F on a faulty torus.

The torus metric carries Eq. (1)'s fault term when it is given the
(ndim, F) table ``pen`` of the penalised nodes' coordinates:

    w(u, v) = hops(u, v) + 100 * badlinks(u, v)

``badlinks`` counts the links of the dimension-ordered route (shortest
wrap per dimension, ties toward +1) with a penalised endpoint, each link
once.  With the route's nodes ``x_0 = u, ..., x_h = v``, a link
``(x_t, x_t+1)`` is bad when ``x_t+1`` is penalised, or when ``x_t`` is
and ``x_t+1`` is not; so

    badlinks = sum over penalised x on the route of
               [x != u] + [x != v] * [the route's next node is healthy]

Node x is on the route when, for some dimension k, its prefix (dims < k)
matches v, its suffix (dims > k) matches u and ``x[k]`` lies on the
shortest wrap path from ``u[k]`` to ``v[k]`` (the test of
``TorusLazyDistance._on_route_any``).  The next node after x moves x one
step along the first dimension in which x differs from v, in the
route's direction there; whether that neighbour is penalised is read
from the (2 * ndim, F) table of :func:`torus_pen_neighbours`.  Every
value is a small integer, so float32 holds it exactly.

Columns of ``pen`` past the real penalised nodes hold -1, which matches
no coordinate: F is padded to a power of two so that a new fault draw
reuses the compiled programs.
"""
from __future__ import annotations

import jax.numpy as jnp

# the paper's "100" in Eq. (1) (``repro.core.topology.FAULT_PENALTY``)
FAULT_PENALTY = 100.0


def wrap(x, d):
    """``x mod d`` for ``x`` in ``[-d, d)``, branchless (no ``rem``)."""
    return jnp.where(x < 0, x + d, x)


def torus_pen_neighbours(pen, dims):
    """(ndim, F) penalised coordinates -> (2 * ndim, F) 0/1 table: row
    ``k`` says whether the node one step ``+1`` along dimension ``k`` is
    penalised too, row ``ndim + k`` the same for ``-1``."""
    rows = []
    for step in (1, -1):
        for k, d in enumerate(dims):
            moved = pen.at[k].set(jnp.mod(pen[k] + step, d))
            hit = jnp.all(moved[:, :, None] == pen[:, None, :], axis=0)
            rows.append(jnp.any(hit, axis=1))
    return jnp.stack(rows).astype(pen.dtype)


def torus_badlinks_ref(cu, cv, dims, pen, nb=None):
    """Links with a penalised endpoint on the dimension-ordered route
    ``u -> v``, broadcast-elementwise over ``(..., ndim)`` coordinates,
    vectorised over the penalised table's F columns."""
    if nb is None:
        nb = torus_pen_neighbours(pen, dims)
    ndim = len(dims)
    a = [cu[..., k, None] for k in range(ndim)]           # (..., 1)
    b = [cv[..., k, None] for k in range(ndim)]
    x = [pen[k] for k in range(ndim)]                     # (F,)
    post = [None] * (ndim + 1)                            # x matches u
    post[ndim] = x[0] >= 0                                # past dims >= k
    for j in range(ndim - 1, -1, -1):
        post[j] = post[j + 1] & (a[j] == x[j])
    pre = True                                            # x matches v
    on = False                                            # on dims < k
    succ_bad = 0
    for k, d in enumerate(dims):
        fwd = wrap(b[k] - a[k], d)
        bwd = wrap(a[k] - b[k], d)
        plus = fwd <= bwd
        on_k = jnp.where(plus, wrap(x[k] - a[k], d) <= fwd,
                         wrap(a[k] - x[k], d) <= bwd)
        on = on | (post[k + 1] & pre & on_k)
        first = pre & (x[k] != b[k])     # k: first dim where x != v
        succ_bad = succ_bad + (first & (jnp.where(plus, nb[k],
                                                  nb[ndim + k]) > 0))
        pre = pre & (x[k] == b[k])
    per = ~post[0] * 1 + ~pre * 1 - succ_bad
    return jnp.where(on, per, 0).sum(-1).astype(cu.dtype)


def torus_hop_elems_ref(cu, cv, dims, pen=None, nb=None):
    """Broadcast-elementwise torus distance: ``(..., ndim)`` coords in,
    ``(...)`` out (same broadcasting contract as the NumPy fallback).
    Hops alone, or with the (ndim, F) penalised table ``pen`` (and its
    neighbour table ``nb``, computed here when not given) Eq. (1)'s
    ``hops + 100 * badlinks``."""
    out = None
    for k, d in enumerate(dims):
        diff = jnp.abs(cu[..., k] - cv[..., k])
        h = jnp.minimum(diff, d - diff)
        out = h if out is None else out + h
    if pen is None or pen.shape[1] == 0:
        return out
    return out + FAULT_PENALTY * torus_badlinks_ref(cu, cv, dims, pen, nb)


def torus_hop_pairs_ref(cu, cv, dims, pen=None, nb=None):
    """All-pairs form: (m, ndim), (k, ndim) -> (m, k)."""
    return torus_hop_elems_ref(cu[:, None, :], cv[None, :, :], dims,
                               pen, nb)


def fattree_hop_elems_ref(cu, cv):
    """Broadcast-elementwise fat-tree hop count from (pod, edge, host)
    coordinate triples: 0 same host, 2 same edge switch, 4 same pod,
    6 across pods.  Written branchless — each matching level subtracts
    2 hops, and the masks nest (same edge implies same pod) — so the
    values are the exact small integers of the NumPy fallback."""
    same_pod = cu[..., 0] == cv[..., 0]
    same_edge = same_pod & (cu[..., 1] == cv[..., 1])
    same_host = same_edge & (cu[..., 2] == cv[..., 2])
    return 6.0 - 2.0 * same_pod - 2.0 * same_edge - 2.0 * same_host


def fattree_hop_pairs_ref(cu, cv):
    """All-pairs form: (m, 3), (k, 3) -> (m, k)."""
    return fattree_hop_elems_ref(cu[:, None, :], cv[None, :, :])
