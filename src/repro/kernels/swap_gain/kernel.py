"""Fused swap select — Pallas TPU kernel.

One step of the dense pairwise-swap refiner: the gains row of mover
``i`` (two matvecs against the mover's guest and distance rows plus a
fused elementwise combine, see :mod:`.ref`), its masked argmax, and the
accept-or-identity decision.  ``M`` and ``G`` are tiled into row blocks
resident in VMEM, the mover's rows (``Mi``, ``Gi``) are broadcast to
every block, and each gains block is reduced to a running (best gain,
argmax) pair *inside* the kernel — the output blocks are revisited
across the sequential TPU grid, so the n-length gains row never exists
outside VMEM.  The final grid step applies the decision, and the
refiner's ``lax.while_loop`` consumes two scalars per mover.

The mover's rows are dynamic-sliced out on the host side (the *gather*
half of the op).  ``Mi``/``Gi`` are fed twice — once full-width for the
dot products, once as the current column block for the fused
elementwise term — so the kernel body needs no dynamic gathers.  Both
matvecs are ``(1, n) x (block, n)^T`` contractions, so each lands
directly in the lane-major ``(1, block)`` layout, at full f32 precision
on the MXU (the default single-pass bf16 would round guest byte counts
of ~1e7).  The TPU stores no vector value to a scalar location, so the
running pair lives in lane-wide ``(1, 128)`` vector blocks, and the
scalar inputs (mover ``i``, ``n_valid``) are read from SMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.swap_gain.ref import GAIN_EPS

_LANES = 128
# VMEM the double-buffered (block_rows, n) M and G row blocks may take
# together at the default height; the rest of v5e's 16 MiB default scoped
# limit holds the full-width mover rows and the small blocks.  n = 4096 at
# f32 with 256 rows (16 MiB of row blocks) is refused by the compiler.
_ROW_BLOCK_BYTES = 8 << 20
_VMEM_HEADROOM = 8 << 20


def _block_rows(n: int, itemsize: int, block_rows: int | None) -> int:
    """Row-block height: the caller's, else 256, or 128 (the lane width,
    the least a ``(1, block_rows)`` block may have) where 256 rows would
    overflow ``_ROW_BLOCK_BYTES``."""
    if block_rows is None:
        block_rows = 256 if 4 * 256 * n * itemsize <= _ROW_BLOCK_BYTES \
            else _LANES
    return min(block_rows, n)


def _compiler_params(block_rows: int, n: int, itemsize: int):
    """Scoped-VMEM limit raised to fit the row blocks where they alone
    pass ``_ROW_BLOCK_BYTES`` (dense guests past n = 4096 at f32)."""
    need = 4 * block_rows * n * itemsize + _VMEM_HEADROOM
    return pltpu.CompilerParams(vmem_limit_bytes=max(need, 16 << 20))


def _row_dot(row, block):
    """(1, n) x (block, n)^T -> (1, block), f32-exact on the MXU."""
    return jax.lax.dot_general(
        row, block, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=block.dtype)


def _gains_block(m_ref, g_ref, mi_ref, gi_ref, mib_ref, gib_ref, c_ref,
                 ci_ref):
    """(1, block) slice of the gains row for the current row block."""
    a = _row_dot(gi_ref[...], m_ref[...])                # (M @ G[i])[block]
    b = _row_dot(mi_ref[...], g_ref[...])                # (G @ M[i])[block]
    return (ci_ref[...] + c_ref[...]
            - 2.0 * gib_ref[...] * mib_ref[...] - a - b)


def _swap_select_kernel(iv_ref, m_ref, g_ref, mi_ref, gi_ref, mib_ref,
                        gib_ref, c_ref, ci_ref, best_ref, j_ref):
    r = pl.program_id(0)
    last = pl.num_programs(0) - 1
    block = m_ref.shape[0]
    i = iv_ref[0, 0]
    n_valid = iv_ref[0, 1]
    gains = _gains_block(m_ref, g_ref, mi_ref, gi_ref, mib_ref, gib_ref,
                         c_ref, ci_ref)
    col = (r * block
           + jax.lax.broadcasted_iota(jnp.int32, (1, block), dimension=1))
    # the refine-loop mask: identity swap scores 0, padding scores -inf
    gains = jnp.where(col == i, 0.0, gains)
    gains = jnp.where(col < n_valid, gains, -jnp.inf)
    bv = jnp.max(gains, axis=1, keepdims=True)            # (1, 1)
    # first-occurrence argmax, as a masked min over column ids
    bj = jnp.min(jnp.where(gains == bv, col, jnp.iinfo(jnp.int32).max),
                 axis=1, keepdims=True)
    bv = jnp.broadcast_to(bv, best_ref.shape)
    bj = jnp.broadcast_to(bj, j_ref.shape)

    @pl.when(r == 0)
    def _():
        best_ref[...] = bv
        j_ref[...] = bj

    @pl.when(r > 0)
    def _():
        # strictly-greater update keeps the earlier block on ties — the
        # first-occurrence argmax semantics of the reference
        better = bv > best_ref[...]
        best_ref[...] = jnp.where(better, bv, best_ref[...])
        j_ref[...] = jnp.where(better, bj, j_ref[...])

    @pl.when(r == last)
    def _():
        # the apply decision: reject (identity swap j := i) unless the
        # best gain clears the acceptance threshold and i is a live mover
        ok = (best_ref[...] > GAIN_EPS) & (i < n_valid)
        j_ref[...] = jnp.where(ok, j_ref[...], i)


def swap_select_tpu(M, G, contrib, i, n_valid,
                    block_rows: int | None = None,
                    interpret: bool = False):
    """Fused (gains row -> masked argmax -> accept-or-identity) step;
    returns ``(gain, j)`` scalars — see :func:`.ref.swap_select_ref`."""
    n = M.shape[0]
    block_rows = _block_rows(n, M.dtype.itemsize, block_rows)
    pad = (-n) % block_rows
    Mi = jax.lax.dynamic_slice_in_dim(M, i, 1, axis=0)      # (1, n)
    Gi = jax.lax.dynamic_slice_in_dim(G, i, 1, axis=0)
    ci = jax.lax.dynamic_slice_in_dim(contrib, i, 1)
    if pad:
        # square zero-padding: the extra K-dim zeros contribute exactly
        # nothing to the dots, and padded gain columns are masked off
        M = jnp.pad(M, ((0, pad), (0, pad)))
        G = jnp.pad(G, ((0, pad), (0, pad)))
        contrib = jnp.pad(contrib, (0, pad))
        Mi = jnp.pad(Mi, ((0, 0), (0, pad)))
        Gi = jnp.pad(Gi, ((0, 0), (0, pad)))
    np_ = M.shape[0]
    # 2-D, so that under vmap the SMEM block's last two dims stay whole
    iv = jnp.stack([jnp.asarray(i, jnp.int32),
                    jnp.asarray(n_valid, jnp.int32)]).reshape(1, 2)
    lane = pl.BlockSpec((1, _LANES), lambda r: (0, 0))
    best, j = pl.pallas_call(
        _swap_select_kernel,
        grid=(np_ // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # i, n_valid
            pl.BlockSpec((block_rows, np_), lambda r: (r, 0)),   # M rows
            pl.BlockSpec((block_rows, np_), lambda r: (r, 0)),   # G rows
            pl.BlockSpec((1, np_), lambda r: (0, 0)),            # Mi full
            pl.BlockSpec((1, np_), lambda r: (0, 0)),            # Gi full
            pl.BlockSpec((1, block_rows), lambda r: (0, r)),     # Mi block
            pl.BlockSpec((1, block_rows), lambda r: (0, r)),     # Gi block
            pl.BlockSpec((1, block_rows), lambda r: (0, r)),     # contrib
            pl.BlockSpec((1, 1), lambda r: (0, 0)),              # contrib[i]
        ],
        out_specs=(lane, lane),
        out_shape=(jax.ShapeDtypeStruct((1, _LANES), M.dtype),
                   jax.ShapeDtypeStruct((1, _LANES), jnp.int32)),
        compiler_params=_compiler_params(block_rows, np_,
                                         M.dtype.itemsize),
        interpret=interpret,
    )(iv, M, G, Mi, Gi, Mi, Gi, contrib.reshape(1, np_),
      ci.reshape(1, 1))
    return best[0, 0], j[0, 0]
