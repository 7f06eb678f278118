"""Flash attention — Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the CUDA flash-attention tiling (warps over
shared memory) becomes VMEM block tiling driven by BlockSpecs, with the MXU
doing the (block_q x Dh) @ (Dh x block_k) and (block_q x block_k) @
(block_k x Dh) matmuls.  The kv-block grid axis is the innermost,
*sequential* ("arbitrary") dimension: the online-softmax running state
(m, l, acc) lives in VMEM scratch and persists across kv steps; causal
upper-triangle blocks are skipped entirely via ``pl.when``.

Block sizes default to (512, 512): with Dh <= 256 the working set
  q (512 x 256) + k,v (2 x 512 x 256) + acc (512 x 256 f32) + scores
stays well under the ~16 MB v5e VMEM budget and all matmul dims are
multiples of the 128-lane MXU tile.

Validated against ref.py in interpret mode (CPU) by tests/test_kernels_*.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, sk: int, sq: int, block_q: int,
                  block_k: int, num_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k
    offset = sk - sq  # causal end-alignment (unpadded lengths)

    # visit the block unless it lies entirely above the causal diagonal
    run = (k_start <= q_start + block_q - 1 + offset) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            qpos = q_start + offset + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        s = jnp.where(kpos < sk, s, NEG_INF)       # key padding

        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == num_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_tpu(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q (B,H,Sq,Dh), k/v (B,Hkv,Sk,Dh) -> (B,H,Sq,Dh).  GQA folded into
    the index maps (no materialised repeat of K/V)."""
    B, H, Sq, Dh = q.shape
    _, Hkv, Sk, _ = k.shape
    groups = max(H // Hkv, 1)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, causal=causal, sk=Sk, sq=Sq, block_q=block_q,
        block_k=block_k, num_kv=nk)

    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, i, j: (b, h // groups, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, i, j: (b, h // groups, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=cparams,
    )(q, k, v)
    return out[:, :, :Sq]
