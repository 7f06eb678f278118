"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the Triton SSD kernel's SM-parallel chunk
matmuls become MXU matmuls on VMEM blocks; the inter-chunk state recurrence
— the part GPUs handle with grid-sync tricks — maps naturally onto a
*sequential* innermost grid axis with the running (P x N) state held in
VMEM scratch across chunk steps (same pattern as flash attention's online
softmax, which is exactly the state-space-duality point of the paper).

Inputs are pre-conditioned by ops.py: ``xdt = x * dt`` and ``dA = dt * A``
so the kernel sees only tensor contractions:

  intra-chunk: y  = tril(C B^T * L) @ xdt          (Q x Q on the MXU)
  carry-in:    y += (C * exp(cumsum dA)) @ state^T
  state:       state' = exp(sum dA) state + (xdt * decay)^T @ B

Block alignment: chunk Q defaults to 128 (MXU tile), P = head_dim (64 or
128), N = d_state (64/128) — all lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, st_out_ref, state_ref,
                *, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0, 0].astype(jnp.float32)      # (Q, P)
    dA = dA_ref[0, 0].astype(jnp.float32)        # (Q,)
    B = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Q = dA.shape[0]

    dA_cs = jnp.cumsum(dA)                       # (Q,)
    # L[i, j] = exp(dA_cs[i] - dA_cs[j]) for j <= i (segment products)
    diff = dA_cs[:, None] - dA_cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(jj <= ii, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    state = state_ref[...]                       # (P, N)
    c_in = C * jnp.exp(dA_cs)[:, None]           # (Q, N)
    y = y + jax.lax.dot_general(c_in, state,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    decay_to_end = jnp.exp(dA_cs[-1] - dA_cs)    # (Q,)
    state_new = state * jnp.exp(dA_cs[-1]) + jax.lax.dot_general(
        xdt * decay_to_end[:, None], B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = state_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        st_out_ref[0, 0] = state_new.astype(st_out_ref.dtype)


def ssd_scan_tpu(xdt, dA, B, C, chunk: int = 128, interpret: bool = False):
    """xdt (B,H,S,P), dA (B,H,S), B/C (B,G,S,N) -> y (B,H,S,P),
    final_state (B,H,P,N)."""
    b, H, S, P = xdt.shape
    G, N = B.shape[1], B.shape[3]
    groups = max(H // G, 1)
    chunk = min(chunk, S)
    assert S % chunk == 0, f"S={S} % chunk={chunk}"
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    y, st = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, h, c: (i, h, c)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda i, h, c: (i, h // groups, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda i, h, c: (i, h // groups, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xdt.shape, xdt.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
    )(xdt, dA, B, C)
    return y, st
