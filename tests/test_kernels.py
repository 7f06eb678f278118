"""Pallas kernel tests: interpret-mode execution vs pure-jnp oracles,
shape/dtype sweeps + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_tpu
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_tpu
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.ssm import ssd_chunked

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _naive_attention(q, k, v, causal):
    import math
    H, Hkv = q.shape[1], k.shape[1]
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("bhsk,bhtk->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        qp = jnp.arange(Sq) + (Sk - Sq)
        kp = jnp.arange(Sk)
        s = jnp.where(kp[None, :] <= qp[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtk->bhsk", p, v.astype(jnp.float32))


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,Dh,causal", [
    (1, 2, 2, 64, 64, 32, True),
    (2, 4, 2, 96, 96, 64, True),      # GQA + non-pow2 seq (padding)
    (1, 4, 1, 32, 128, 64, True),     # decode-ish: Sq < Sk, MQA
    (2, 2, 2, 64, 64, 128, False),    # non-causal (cross attention)
    (1, 8, 4, 200, 200, 64, True),    # ragged tail
])
def test_flash_kernel_matches_ref(B, H, Hkv, Sq, Sk, Dh, causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, Dh), dtype)
    out_k = flash_attention_tpu(q, k, v, causal=causal, block_q=32,
                                block_k=32, interpret=True)
    out_r = flash_attention_ref(q, k, v, causal=causal, q_block=16,
                                kv_block=32)
    naive = _naive_attention(q, k, v, causal)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(naive), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(out_r, np.float32),
                               np.asarray(naive), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(
    sq=st.integers(4, 80), dh=st.sampled_from([16, 32, 64]),
    h=st.sampled_from([1, 2, 4]), seed=st.integers(0, 100),
)
def test_flash_kernel_property(sq, dh, h, seed):
    """Any shape: kernel == oracle == naive within fp tolerance."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (1, h, sq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (1, h, sq, dh), jnp.float32)
    v = jax.random.normal(ks[2], (1, h, sq, dh), jnp.float32)
    out_k = flash_attention_tpu(q, k, v, causal=True, block_q=16,
                                block_k=16, interpret=True)
    naive = _naive_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(naive),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,G,S,P,N,chunk", [
    (1, 2, 1, 64, 16, 16, 16),
    (2, 4, 2, 128, 32, 32, 32),
    (1, 8, 1, 96, 64, 128, 32),   # grouped broadcast, wide state
])
def test_ssd_kernel_matches_ref(B, H, G, S, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(1), 4)
    xdt = jax.random.normal(ks[0], (B, H, S, P), dtype) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.5
    dA = dA.astype(dtype)
    Bm = jax.random.normal(ks[2], (B, G, S, N), dtype) * 0.5
    Cm = jax.random.normal(ks[3], (B, G, S, N), dtype) * 0.5
    y_k, st_k = ssd_scan_tpu(xdt, dA, Bm, Cm, chunk=chunk, interpret=True)
    y_r, st_r = ssd_scan_ref(xdt, dA, Bm, Cm, chunk=chunk)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st_k, np.float32),
                               np.asarray(st_r, np.float32),
                               atol=tol, rtol=tol)


def test_ssd_model_chunked_matches_direct_recurrence():
    """models/ssm.ssd_chunked (used by mamba2/zamba2) == exact recurrence."""
    ks = jax.random.split(jax.random.key(2), 5)
    B, S, H, P, G, N = 2, 64, 4, 16, 1, 32
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y, fin = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)

    from repro.kernels.ssd_scan.ref import _direct
    xdt = jnp.moveaxis(x * dt[..., None], 1, 2)
    dA = jnp.moveaxis(dt * A[None, None, :], 1, 2)
    y_d, fin_d = _direct(xdt, dA, jnp.moveaxis(Bm, 1, 2),
                         jnp.moveaxis(Cm, 1, 2))
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.moveaxis(y_d, 1, 2)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_d),
                               atol=1e-4, rtol=1e-4)


def test_ssd_decode_consistent_with_scan():
    """Running ssd_chunked over S tokens == S single decode steps."""
    from repro.models.ssm import ssd_decode_step
    ks = jax.random.split(jax.random.key(3), 5)
    B, S, H, P, G, N = 1, 8, 2, 8, 1, 16
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_scan, fin = ssd_chunked(x, dt, A, Bm, Cm, chunk=4)
    state2 = jnp.zeros((B, H, P, N))
    outs = []
    for t in range(S):
        y_t, state2 = ssd_decode_step(state2, x[:, t], dt[:, t], A,
                                      Bm[:, t], Cm[:, t])
        outs.append(y_t)
    y_step = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(state2),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 128), (130, 256)])
def test_rmsnorm_kernel_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.key(4), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], shape[-1:], dtype) + 1.0
    out_k = rmsnorm_tpu(x, w, interpret=True, block_rows=8)
    out_r = rmsnorm_ref(x, w)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 64), d=st.sampled_from([32, 128, 512]),
       seed=st.integers(0, 50))
def test_rmsnorm_property(rows, d, seed):
    ks = jax.random.split(jax.random.key(seed), 2)
    x = jax.random.normal(ks[0], (rows, d))
    w = jax.random.normal(ks[1], (d,)) + 1.0
    out_k = rmsnorm_tpu(x, w, interpret=True, block_rows=16)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(rmsnorm_ref(x, w)),
                               atol=2e-5, rtol=2e-5)
    # invariance: rmsnorm(c*x) == rmsnorm(x) for any positive scale c
    out_s = rmsnorm_tpu(3.7 * x, w, interpret=True, block_rows=16)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_k),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- swap_gain
@pytest.mark.parametrize("n,block_rows", [(64, 64), (200, 64), (256, 128)])
def test_swap_select_kernel_matches_ref_f32(n, block_rows):
    """Real-valued f32 inputs, as on the chip: the kernel's best gain
    matches the reference's, and its partner is a best swap of the
    float64 gains row (near-ties may legitimately resolve either way)."""
    from repro.kernels.swap_gain.kernel import swap_select_tpu
    from repro.kernels.swap_gain.ref import swap_select_ref

    rng = np.random.default_rng(0)
    M = rng.random((n, n))
    M = 0.5 * (M + M.T)
    G = rng.random((n, n)) * (rng.random((n, n)) < 0.2)
    G = 0.5 * (G + G.T)
    contrib = (G * M).sum(1)
    args32 = [jnp.asarray(a, jnp.float32) for a in (M, G, contrib)]
    tol = 2e-4 * float(n)
    for i in (0, n // 2, n - 1):
        want_gain, _ = _select_oracle(M, G, contrib, i, n)
        ref_gain, _ = swap_select_ref(*args32, jnp.int32(i), jnp.int32(n))
        gain, j = swap_select_tpu(*args32, jnp.int32(i), jnp.int32(n),
                                  block_rows=block_rows, interpret=True)
        np.testing.assert_allclose(float(gain), float(ref_gain),
                                   rtol=2e-4, atol=tol)
        np.testing.assert_allclose(float(gain), want_gain,
                                   rtol=2e-4, atol=tol)
        row = _gains_row(M, G, contrib, i, n)
        assert row[int(j)] >= want_gain - tol, (n, i)


def _gains_row(M, G, contrib, i, n_valid):
    """The masked float64 gains row the select oracle takes its max of
    (numpy, independent of the jitted reference)."""
    M, G, contrib = (np.asarray(a, np.float64) for a in (M, G, contrib))
    g = (contrib[i] + contrib - 2.0 * G[i] * M[i]
         - M @ G[i] - G @ M[i])
    g[i] = 0.0
    g[n_valid:] = -np.inf
    return g


def _select_oracle(M, G, contrib, i, n_valid):
    """Composed oracle for the fused select: full gains row, mask, argmax,
    accept-or-identity — the exact steps the fused kernel collapses."""
    from repro.kernels.swap_gain.ref import GAIN_EPS

    g = _gains_row(M, G, contrib, i, n_valid)
    j = int(np.argmax(g))
    gain = float(g[j])
    if not (gain > GAIN_EPS and i < n_valid):
        j = i
    return gain, j


@pytest.mark.parametrize("n,n_valid,block_rows", [
    (16, 16, 8),        # single block
    (64, 64, 64),       # block == n
    (200, 180, 64),     # ragged + padded tail beyond n_valid
    (300, 256, 128),    # multi-block with padding
])
def test_swap_select_triad(n, n_valid, block_rows):
    """Fused mover select: ref == Pallas-interpret == composed oracle,
    including first-occurrence argmax ties (integer weights make exact
    duplicate gains common at these sizes)."""
    from repro.kernels.swap_gain.kernel import swap_select_tpu
    from repro.kernels.swap_gain.ref import swap_select_ref

    rng = np.random.default_rng(0)
    A = rng.integers(0, 7, (n, n)).astype(np.float64)
    M = A + A.T
    B = (rng.integers(0, 5, (n, n)) * (rng.random((n, n)) < 0.3))
    G = (B + B.T).astype(np.float64)
    contrib = (G * M).sum(1)
    for i in (0, n // 3, n_valid - 1, n - 1):
        want_gain, want_j = _select_oracle(M, G, contrib, i, n_valid)
        for fn in (
            swap_select_ref,
            lambda *a: swap_select_tpu(*a, block_rows=block_rows,
                                       interpret=True),
        ):
            gain, j = fn(jnp.asarray(M), jnp.asarray(G),
                         jnp.asarray(contrib), jnp.int32(i),
                         jnp.int32(n_valid))
            assert int(j) == want_j, (n, i)
            if want_j != i:            # gain only meaningful on accept
                np.testing.assert_allclose(float(gain), want_gain,
                                           rtol=1e-12)


def test_swap_select_rejects_all_negative():
    """No positive gain anywhere -> j == i (identity swap), every impl."""
    from repro.kernels.swap_gain.kernel import swap_select_tpu
    from repro.kernels.swap_gain.ops import swap_select
    from repro.kernels.swap_gain.ref import swap_select_ref

    n = 32
    # an already-optimal layout: identical processes, so every swap gain
    # is exactly zero (< GAIN_EPS) and the mover must stay put
    M = np.ones((n, n)) - np.eye(n)
    G = np.ones((n, n)) - np.eye(n)
    contrib = (G * M).sum(1)
    args = (jnp.asarray(M), jnp.asarray(G), jnp.asarray(contrib),
            jnp.int32(3), jnp.int32(n))
    for fn in (swap_select_ref, swap_select,
               lambda *a: swap_select_tpu(*a, interpret=True)):
        _, j = fn(*args)
        assert int(j) == 3


def test_swap_select_ops_dispatch():
    """auto resolves to the jitted ref off-TPU; the dense refine path of
    the jax mapping backend consumes exactly this entry point."""
    from repro.kernels.swap_gain.ops import swap_select
    from repro.kernels.swap_gain.ref import swap_select_ref

    rng = np.random.default_rng(1)
    n = 48
    M = jnp.asarray(0.5 * (rng.random((n, n)) + rng.random((n, n)).T))
    G = jnp.asarray(rng.integers(0, 5, (n, n)).astype(np.float64))
    G = 0.5 * (G + G.T)
    contrib = (G * M).sum(1)
    args = (M, G, contrib, jnp.int32(7), jnp.int32(n))
    gain, j = swap_select(*args)
    want_gain, want_j = swap_select_ref(*args)
    assert int(j) == int(want_j)
    np.testing.assert_allclose(float(gain), float(want_gain), rtol=1e-12)


# ------------------------------------------------------------- hop_dist
@pytest.mark.parametrize("dims,m,k", [
    ((8, 8, 8), 37, 53),       # ragged (padding exercised)
    ((32, 32, 16), 256, 128),  # block-aligned
    ((5, 7), 12, 12),          # 2-D, non-pow2 extents
    ((2, 3, 4, 3), 9, 30),     # 4-D
])
def test_torus_hop_kernel_matches_np(dims, m, k):
    from repro.kernels.hop_dist.kernel import torus_hop_tpu
    from repro.kernels.hop_dist.ops import torus_hop_pairs, torus_hop_pairs_np
    from repro.kernels.hop_dist.ref import torus_hop_pairs_ref

    rng = np.random.default_rng(0)
    cu = np.stack([rng.integers(0, d, m) for d in dims], axis=1)
    cv = np.stack([rng.integers(0, d, k) for d in dims], axis=1)
    want = torus_hop_pairs_np(cu, cv, dims)  # numpy all-pairs oracle
    got_ref = np.asarray(torus_hop_pairs_ref(jnp.asarray(cu),
                                             jnp.asarray(cv), dims))
    got_tpu = np.asarray(torus_hop_tpu(jnp.asarray(cu), jnp.asarray(cv),
                                       dims, interpret=True))
    got_auto = np.asarray(torus_hop_pairs(cu, cv, dims))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_tpu, want)
    np.testing.assert_array_equal(got_auto, want)


def test_torus_hop_elems_matches_dense_hop_matrix():
    from repro.core.topology import TorusTopology
    from repro.kernels.hop_dist.ops import torus_hop_np
    from repro.kernels.hop_dist.ref import torus_hop_elems_ref

    topo = TorusTopology((6, 5, 4))
    c = topo.coords_array()
    H = topo.hop_matrix()
    u, v = np.meshgrid(np.arange(120), np.arange(120), indexing="ij")
    np.testing.assert_array_equal(
        torus_hop_np(c[u.ravel()], c[v.ravel()],
                     topo.dims).reshape(120, 120), H)
    got = np.asarray(torus_hop_elems_ref(
        jnp.asarray(c[u.ravel()]), jnp.asarray(c[v.ravel()]), topo.dims))
    np.testing.assert_array_equal(got.reshape(120, 120), H)


@pytest.mark.parametrize("k,m,kk", [
    (4, 16, 16),       # tiny pod structure
    (6, 37, 53),       # ragged (padding exercised)
    (8, 128, 100),     # block-aligned rows, ragged cols
])
def test_fattree_hop_triad(k, m, kk):
    """np == jitted ref == Pallas-interpret on the fat-tree metric, all
    checked against the topology's dense hop matrix."""
    from repro.core.fattree import FatTreeTopology
    from repro.kernels.hop_dist.kernel import fattree_hop_tpu
    from repro.kernels.hop_dist.ops import (fattree_hop, fattree_hop_pairs_np)
    from repro.kernels.hop_dist.ref import fattree_hop_pairs_ref

    topo = FatTreeTopology(k)
    c = topo.coords_array().astype(np.float64)
    rng = np.random.default_rng(0)
    u = rng.integers(0, topo.n_nodes, m)
    v = rng.integers(0, topo.n_nodes, kk)
    want = topo.hop_matrix()[np.ix_(u, v)].astype(np.float64)
    np.testing.assert_array_equal(fattree_hop_pairs_np(c[u], c[v]), want)
    np.testing.assert_array_equal(
        np.asarray(fattree_hop_pairs_ref(jnp.asarray(c[u]),
                                         jnp.asarray(c[v]))), want)
    np.testing.assert_array_equal(
        np.asarray(fattree_hop_tpu(jnp.asarray(c[u]), jnp.asarray(c[v]),
                                   interpret=True)), want)
    np.testing.assert_array_equal(np.asarray(fattree_hop(c[u], c[v])), want)


def test_fattree_hop_elems_matches_lazy_adapter():
    """The elementwise form agrees with FatTreeLazyDistance under scale
    and endpoint penalties (the exact metric the jitted refine compiles)."""
    from repro.core.fattree import FatTreeTopology
    from repro.kernels.hop_dist.ops import fattree_hop_np
    from repro.kernels.hop_dist.ref import fattree_hop_elems_ref

    topo = FatTreeTopology(4)
    lazy = topo.lazy_distance(c=2.0)
    c = topo.coords_array()
    n = topo.n_nodes
    u, v = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    u, v = u.ravel(), v.ravel()
    got_np = 2.0 * fattree_hop_np(c[u], c[v])
    got_ref = 2.0 * np.asarray(fattree_hop_elems_ref(
        jnp.asarray(c[u]), jnp.asarray(c[v])))
    np.testing.assert_array_equal(got_np, 2.0 * topo.hop_matrix()[u, v])
    np.testing.assert_array_equal(got_ref, got_np)
    np.testing.assert_array_equal(np.asarray(lazy[u, v]), got_np)


@given(st.integers(2, 16), st.integers(2, 16), st.integers(2, 16),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_torus_hop_property(dx, dy, dz, seed):
    from repro.kernels.hop_dist.ops import torus_hop_np

    dims = (dx, dy, dz)
    rng = np.random.default_rng(seed)
    cu = np.stack([rng.integers(0, d, 8) for d in dims], axis=1)
    cv = np.stack([rng.integers(0, d, 8) for d in dims], axis=1)
    h = torus_hop_np(cu, cv, dims)
    assert (h >= 0).all()
    assert (h <= sum(d // 2 for d in dims)).all()             # diameter
    np.testing.assert_array_equal(h, torus_hop_np(cv, cu, dims))  # symmetry
    assert (torus_hop_np(cu, cu, dims) == 0).all()            # identity


# ------------------------------------------------- penalised torus metric
def _scalar_route_weights(topo, penal):
    """Eq. (1) by one scalar walk of ``route_nodes`` per pair: hops plus
    100 per link with a penalised endpoint, each link once."""
    n = topo.n_nodes
    out = np.zeros((n, n))
    for u in range(n):
        for v in range(n):
            nodes = topo.route_nodes(u, v)
            bad = sum(1 for a, b in zip(nodes[:-1], nodes[1:])
                      if penal[a] or penal[b])
            out[u, v] = (len(nodes) - 1) + 100.0 * bad
    return out


@pytest.mark.parametrize("dims,faulty", [
    # adjacent faults along every dimension, the extent-2 ring included
    ((4, 3, 5, 2), [0, 1, 2, 10, 30, 37, 119]),
    # a 2x2 square of faults (links between two faulty nodes), extent 3
    ((3, 4, 3), [0, 1, 3, 4, 13, 35]),
    ((3, 4, 3), [17]),                       # one fault: F = 1, no padding
    ((4, 3, 5, 2), [5, 6]),                  # two adjacent faults, F = 2
], ids=["4x3x5x2-adjacent", "3x4x3-square", "3x4x3-one", "4x3x5x2-pair"])
def test_penalised_torus_hop_matches_dense_and_scalar_walk(dims, faulty):
    """Kernel (interpret), jnp reference (all-pairs, elementwise and row
    forms), the lazy adapter and the dense ``weight_matrix`` all equal
    the scalar route walk, over all pairs: faults on route endpoints and
    u == v included."""
    from repro.core.topology import TorusTopology
    from repro.kernels.hop_dist.kernel import torus_hop_tpu
    from repro.kernels.hop_dist.ops import torus_hop_pairs
    from repro.kernels.hop_dist.ref import (torus_hop_elems_ref,
                                            torus_hop_pairs_ref)

    topo = TorusTopology(dims)
    n = topo.n_nodes
    p_f = np.zeros(n)
    p_f[faulty] = 0.02
    want = _scalar_route_weights(topo, p_f > 0)
    np.testing.assert_array_equal(topo.weight_matrix(p_f), want)
    lazy = topo.lazy_distance(p_f)
    u, v = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    np.testing.assert_array_equal(lazy[u, v], want)
    spec = lazy.implicit
    f_pad = 1 << (len(faulty) - 1).bit_length()
    assert spec.penalised.shape == (len(dims), f_pad)
    c, pen = jnp.asarray(spec.coords), jnp.asarray(spec.penalised)
    got = {
        "kernel": torus_hop_tpu(c, c, dims, pen, interpret=True),
        "pairs_ref": torus_hop_pairs_ref(c, c, dims, pen),
        "auto": torus_hop_pairs(c, c, dims, pen),
        "elems_ref": torus_hop_elems_ref(c[u.ravel()], c[v.ravel()], dims,
                                         pen).reshape(n, n),
        "rows_ref": jnp.stack([torus_hop_elems_ref(c[i], c, dims, pen)
                               for i in range(n)]),
    }
    for name, value in got.items():
        np.testing.assert_array_equal(np.asarray(value), want, err_msg=name)
