"""Implicit hop distance — Pallas TPU kernels (torus and fat-tree).

Computes an (m, k) block of hop distances directly from the coordinate
tables, so the mapping hot path never gathers from (or materialises) a
stored O(N^2) matrix.  Coordinates are fed transposed — ``(ndim, m)`` /
``(ndim, k)`` — so the large axis is the TPU lane dimension; each kernel
tiles the ``cu`` side into row blocks resident in VMEM, keeps the full
``cv`` table broadcast to every block, and evaluates its metric inline:
the torus kernel unrolls the per-dimension min(|d|, dim-|d|)
accumulation at trace time (``dims`` is static, 2–4 entries for the
in-tree tori); the fat-tree kernel nests the (pod, edge, host) level
matches branchlessly.  One write per output block, no dynamic gathers
in the bodies.

On a faulty torus the kernel adds Eq. (1)'s fault term (see
:mod:`.ref` for the closed form): the (ndim, F) table of penalised
coordinates and its (2 * ndim, F) neighbour table are read as scalars
from SMEM, and the F penalised nodes are unrolled at trace time, each
adding its route-membership test to the block.  The Pallas call is
named ``torus_hop`` in the device trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hop_dist.ref import (FAULT_PENALTY, torus_pen_neighbours,
                                        wrap)


def _hop_kernel(dims, n_pen: int):
    ndim = len(dims)

    def body(*refs):
        cu_ref, cv_ref = refs[:2]
        o_ref = refs[-1]
        a = [cu_ref[j, :][:, None] for j in range(ndim)]    # (rows, 1)
        b = [cv_ref[j, :][None, :] for j in range(ndim)]    # (1, k_pad)
        total = None
        for j, d in enumerate(dims):
            diff = jnp.abs(a[j] - b[j])
            h = jnp.minimum(diff, d - diff)
            total = h if total is None else total + h
        if n_pen:
            total = total + FAULT_PENALTY * _badlinks(dims, a, b, *refs[2:4],
                                                      n_pen)
        o_ref[...] = total
    return body


def _badlinks(dims, a, b, pen_ref, nb_ref, n_pen: int):
    """The (rows, k_pad) block of route links with a penalised endpoint:
    :func:`.ref.torus_badlinks_ref` with 0/1 floats for its masks."""
    ndim = len(dims)
    one, zero = 1.0, 0.0
    fwd = [wrap(b[k] - a[k], d) for k, d in enumerate(dims)]
    bwd = [wrap(a[k] - b[k], d) for k, d in enumerate(dims)]
    plus = [f <= w for f, w in zip(fwd, bwd)]
    reach = [jnp.where(p, f, w) for p, f, w in zip(plus, fwd, bwd)]
    bad = None
    for f in range(n_pen):
        x = [pen_ref[k, f] for k in range(ndim)]            # scalars
        post = [None] * (ndim + 1)                          # (rows, 1)
        post[ndim] = jnp.where(x[0] >= 0, one, zero)
        for j in range(ndim - 1, -1, -1):
            post[j] = post[j + 1] * jnp.where(a[j] == x[j], one, zero)
        pre = one                                           # (1, k_pad)
        on = succ_bad = None
        for k, d in enumerate(dims):
            step = jnp.where(plus[k], wrap(x[k] - a[k], d),
                             wrap(a[k] - x[k], d))
            on_k = post[k + 1] * pre * jnp.where(step <= reach[k], one,
                                                 zero)
            first = pre * jnp.where(x[k] != b[k], one, zero)
            s_k = first * jnp.where(plus[k], nb_ref[k, f],
                                    nb_ref[ndim + k, f])
            on = on_k if on is None else jnp.maximum(on, on_k)
            succ_bad = s_k if succ_bad is None else succ_bad + s_k
            pre = pre * jnp.where(x[k] == b[k], one, zero)
        term = on * ((one - post[0]) + (one - pre) - succ_bad)
        bad = term if bad is None else bad + term
    return bad


def torus_hop_tpu(cu, cv, dims, pen=None, block_rows: int | None = None,
                  interpret: bool = False):
    """(m, ndim), (k, ndim) coords -> (m, k) hop distances.

    ``dims`` must be a static tuple (the torus extents).  Accepts int or
    float coordinate arrays; output dtype follows the input (the mapping
    backend feeds float coords in its compute dtype — hop values are
    small integers, exact in float32).  With ``pen``, the (ndim, F)
    penalised table (F > 0), the block is Eq. (1)'s
    ``hops + 100 * badlinks`` (:mod:`.ref`).
    """
    cu = jnp.asarray(cu)
    cv = jnp.asarray(cv)
    m, nd = cu.shape
    k = cv.shape[0]
    assert nd == len(dims) and cv.shape[1] == nd
    n_pen = 0 if pen is None else int(pen.shape[1])
    if block_rows is None:
        # the faulty body holds ~3 * ndim (rows, k_pad) blocks at once;
        # 128 is the least lane-aligned height of the cu block
        block_rows = 128 if n_pen else 256
    block_rows = min(block_rows, max(m, 1))
    cuT = cu.T                                     # (ndim, m)
    cvT = cv.T                                     # (ndim, k)
    pad_m = (-m) % block_rows
    pad_k = (-k) % 128                             # lane-dim alignment
    if pad_m:
        cuT = jnp.pad(cuT, ((0, 0), (0, pad_m)))
    if pad_k:
        cvT = jnp.pad(cvT, ((0, 0), (0, pad_k)))
    m_pad, k_pad = cuT.shape[1], cvT.shape[1]
    grid = (m_pad // block_rows,)
    in_specs = [
        pl.BlockSpec((nd, block_rows), lambda r: (0, r)),  # cu block
        pl.BlockSpec((nd, k_pad), lambda r: (0, 0)),       # cv full
    ]
    operands = [cuT, cvT]
    if n_pen:
        pen = jnp.asarray(pen, dtype=cu.dtype)
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        operands += [pen, torus_pen_neighbours(pen, dims)]

    out = pl.pallas_call(
        _hop_kernel(tuple(dims), n_pen),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, k_pad), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), cu.dtype),
        interpret=interpret,
        name="torus_hop",
    )(*operands)
    return out[:m, :k]


def _fattree_kernel(cu_ref, cv_ref, o_ref):
    # branchless level match: each nested level subtracts 2 hops
    # (identical arithmetic to .ref.fattree_hop_elems_ref)
    same_pod = cu_ref[0, :][:, None] == cv_ref[0, :][None, :]
    same_edge = same_pod & (cu_ref[1, :][:, None] == cv_ref[1, :][None, :])
    same_host = same_edge & (cu_ref[2, :][:, None] == cv_ref[2, :][None, :])
    o_ref[...] = (6.0 - 2.0 * same_pod - 2.0 * same_edge
                  - 2.0 * same_host).astype(o_ref.dtype)


def fattree_hop_tpu(cu, cv, block_rows: int = 256,
                    interpret: bool = False):
    """(m, 3), (k, 3) fat-tree (pod, edge, host) coords -> (m, k) hop
    counts (0/2/4/6); same transposed-coordinate tiling as
    :func:`torus_hop_tpu`."""
    cu = jnp.asarray(cu)
    cv = jnp.asarray(cv)
    m, nd = cu.shape
    k = cv.shape[0]
    assert nd == 3 and cv.shape[1] == 3
    block_rows = min(block_rows, max(m, 1))
    cuT = cu.T                                     # (3, m)
    cvT = cv.T                                     # (3, k)
    pad_m = (-m) % block_rows
    pad_k = (-k) % 128                             # lane-dim alignment
    if pad_m:
        # pad with -1: never equal to a real coordinate, so padded
        # lanes can't alias a real (pod, edge, host) triple
        cuT = jnp.pad(cuT, ((0, 0), (0, pad_m)), constant_values=-1)
    if pad_k:
        cvT = jnp.pad(cvT, ((0, 0), (0, pad_k)), constant_values=-1)
    m_pad, k_pad = cuT.shape[1], cvT.shape[1]
    grid = (m_pad // block_rows,)

    out = pl.pallas_call(
        _fattree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nd, block_rows), lambda r: (0, r)),  # cu block
            pl.BlockSpec((nd, k_pad), lambda r: (0, 0)),       # cv full
        ],
        out_specs=pl.BlockSpec((block_rows, k_pad), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), cu.dtype),
        interpret=interpret,
    )(cuT, cvT)
    return out[:m, :k]
