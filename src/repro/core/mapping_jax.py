"""JIT-compiled JAX implementations of the mapping hot kernels.

This module is only imported when the ``jax`` backend is active
(:mod:`repro.core.backend`); a NumPy-only install never reaches it.  Every
public function mirrors its :mod:`repro.core.mapping` counterpart —
NumPy arrays in, NumPy arrays out — and is **decision-identical** to it:
with the in-tree workloads all guest weights and route distances are
exactly-representable integers, float64 arithmetic on them is exact, and
the kernels below are algebraic rearrangements of the NumPy expressions,
so for ``dtype="float64"`` the same swaps are accepted in the same order
and the returned placements match the NumPy backend bit-for-bit
(``tests/test_backend_diff.py``).

What the port changes is the *cost model*, not the algorithm:

* **Swap-gain scoring is gather+matvec, not dense matvec.**  The guest
  graphs of interest are sparse (NPB-DT at n=1024 has ~3 edges per rank),
  so the per-mover gains row

      gains = contrib[i] + contrib - 2*C[i] - M @ G[i] - G @ M[i]

  is evaluated from the CSR-padded rows of ``G`` in O(n*k) — a k-column
  gather of ``M`` and a k-wide weighted sum — instead of two O(n^2)
  matvecs.  Products against explicit zeros contribute exactly 0.0, so
  the sparse evaluation is bit-equal to the dense one.  Guests denser
  than half-full fall back to a dense-matvec variant of the same loop
  (routed through :mod:`repro.kernels.swap_gain` so TPU runs can use the
  Pallas kernel).
* **All candidates refine in one dispatch.**  ``refine_many`` vmaps the
  refinement loop over a stack of candidate placements (TOFA's windows,
  balls and snake seeds), replacing the per-candidate Python loop with a
  single device call.  Converged candidates are naturally idempotent
  (no improving swap exists), so the batched loop runs until the last
  candidate converges without perturbing the others.
* **The candidate stack shards across devices.**  With more than one
  visible device (``backend.JaxBackend.device_count`` > 1) the stack is
  split along the candidate axis with ``shard_map`` — guest structure
  and distances replicated, batch edge-padded to a device multiple — so
  each device refines only its slice and each shard's ``while_loop``
  stops when *its own* candidates converge.  Candidates never interact,
  so the sharded dispatch is bit-identical to the single-device one
  (``tests/test_sharded_refine.py``).  Two XLA:CPU landmines are worked
  around deliberately: operands are replicated from the **host** (see
  ``_shard_args``) and the mover-order sort is computed without the
  ``sort`` HLO inside sharded executables (see ``_refine_one``'s
  ``sortless`` path).
* **Distance matrices are device-resident.**  Hosts hand the same cached
  (topology, health) matrix object to every placement, and the backend
  keeps its symmetrised device copy alive across jobs, so a batch of
  placements pays one transfer.
* **Shapes are padded to powers of two** (process count, sparse row
  width, candidate count) with masked tails, so mixed job sizes reuse a
  small set of compiled kernels instead of recompiling per size.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.swap_gain.ref import GAIN_EPS

from . import backend as _backend
from . import spans as _spans
from .mapping import REFINE_MOVERS, REFINE_PASSES


# --------------------------------------------------------------------------
# host-side preparation (sparse structure, symmetrised distances, padding)
# --------------------------------------------------------------------------

def _pow2(x: int) -> int:
    return 1 << max(0, int(x - 1)).bit_length() if x > 1 else 1


class _IdLRU:
    """Tiny identity-keyed LRU holding host intermediates alive."""

    def __init__(self, maxlen: int = 8):
        self._d: OrderedDict[int, tuple] = OrderedDict()
        self._maxlen = maxlen

    def get(self, key_obj, fn):
        key = id(key_obj)
        hit = self._d.get(key)
        if hit is not None and hit[0] is key_obj:
            self._d.move_to_end(key)
            return hit[1]
        out = fn()
        self._d[key] = (key_obj, out)   # strong ref pins id()
        while len(self._d) > self._maxlen:
            self._d.popitem(last=False)
        return out


_SPARSE_CACHE = _IdLRU()
_SYM_CACHE = _IdLRU()
_GUEST_OK_CACHE = _IdLRU(maxlen=32)
_SPARSE_DEV_CACHE = _IdLRU()


def guest_supported(G_w: np.ndarray) -> bool:
    """The jitted kernels assume the symmetric-guest convention
    (CommGraph accumulates both directions); asymmetric guests fall back
    to the NumPy kernels at the dispatch layer.  Cached by identity —
    the same guest matrix is scored/refined many times per placement."""
    return _GUEST_OK_CACHE.get(
        G_w, lambda: bool(np.array_equal(G_w, G_w.T)))


def lazy_supported(D) -> bool:
    """A lazy distance adapter is served by this module only when it
    exposes an implicit spec — distances are then computed in-kernel
    (:mod:`repro.kernels.hop_dist`), never gathered from a stored matrix.
    Fat-trees in *any* health state qualify (fault/straggler weighting
    is endpoint-form, so it jits as a penalty-vector gather), and tori
    in any health state without stragglers (Eq. (1)'s penalised links are
    counted in closed form from the table of penalised coordinates);
    straggling tori price links by slowdown and run the NumPy kernels
    instead."""
    return getattr(D, "implicit", None) is not None


def _dist_fns(Ds, dims, scale):
    """The two distance accessors of the refine/score loops, closed over
    either a dense (N, N) matrix (``dims is None``), a ``(coords,
    penalised)`` pair with static torus ``dims`` — the (N, ndim)
    coordinate table and the (ndim, F) table of penalised coordinates of
    :class:`repro.core.lazydist.TorusLazyDistance` (F = 0 when healthy)
    — or, when ``dims`` is the static marker ``("fattree",)``, a
    ``(coords, penalty)`` pair implementing the endpoint-form fat-tree
    metric (:class:`repro.core.lazydist.FatTreeLazyDistance`)."""
    if dims is None:
        def dist_pairs(a, b):
            return Ds[a, b]

        def dist_row(node, p):
            return Ds[node][p]
    elif dims == ("fattree",):
        from repro.kernels.hop_dist.ops import fattree_hop_pairs
        from repro.kernels.hop_dist.ref import fattree_hop_elems_ref
        coords, pen = Ds

        def _at(u, v):
            # c * hops + endpoint penalties — same expression (and
            # summation order) as FatTreeLazyDistance._elems
            hops = scale * fattree_hop_elems_ref(coords[u], coords[v])
            return hops + jnp.where(u != v, pen[u] + pen[v], 0.0)

        dist_pairs = _at

        def dist_row(node, p):
            return _at(node, p)

        def _all_pairs(u, v):
            hops = scale * fattree_hop_pairs(coords[u], coords[v])
            return hops + jnp.where(
                u[:, None] != v[None, :],
                pen[u][:, None] + pen[v][None, :], 0.0)

        dist_pairs.all_pairs = _all_pairs
    else:
        from repro.kernels.hop_dist.ops import torus_hop_pairs
        from repro.kernels.hop_dist.ref import (torus_hop_elems_ref,
                                                torus_pen_neighbours)
        coords, pen = Ds
        # the neighbour table is loop-invariant: built once per program
        nb = torus_pen_neighbours(pen, dims) if pen.shape[1] else None

        def dist_pairs(a, b):
            # broadcast-elementwise; the all-pairs M0 build in
            # _refine_one routes through torus_hop_pairs below instead
            return scale * torus_hop_elems_ref(coords[a], coords[b], dims,
                                               pen, nb)

        def _pairs(u, v):
            return scale * torus_hop_pairs(coords[u], coords[v], dims, pen)

        if nb is None:
            dist_row = dist_pairs
            dist_pairs.all_pairs = _pairs
        else:
            # a penalised link on the route u -> v need not lie on
            # v -> u, so the refine reads the symmetrised weight, as the
            # NumPy loop's gathered_row and M do; scoring keeps D as is
            def dist_row(node, p):
                return 0.5 * (dist_pairs(node, p) + dist_pairs(p, node))

            def _all_pairs(u, v):
                K = _pairs(u, v)
                Kt = K if u is v else _pairs(v, u)
                return 0.5 * (K + Kt.T)

            dist_pairs.all_pairs = _all_pairs
    return dist_pairs, dist_row


def _sparse_rows(G_w: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
    """CSR-padded rows of the (diag-zeroed) guest: (idx, val, k_pad),
    as :func:`_csr_rows` lays them out for one lane."""
    def build():
        G = np.asarray(G_w, dtype=np.float64)
        if np.count_nonzero(np.diagonal(G)):
            G = G.copy()
            np.fill_diagonal(G, 0.0)
        n = G.shape[0]
        r, c = np.nonzero(G)
        idx, val = _csr_rows(np.zeros(len(r), dtype=np.int64), r, c,
                             G[r, c], 1, n)
        return idx[0], val[0], idx.shape[2], G
    return _SPARSE_CACHE.get(G_w, build)


def _csr_rows(lane, row, col, val, S: int, n_rows: int):
    """CSR-padded rows of ``S`` guests from their nonzeros, given as
    (lane, row, column, weight) entries: ``(idx, val)`` of shape
    ``(S, n_rows, k)``, each row listing its columns in ascending order.

    Rows are padded with (index 0, weight 0.0) entries — gathers against
    them multiply by exactly 0.0, so padding never changes a result — to
    one width ``k`` (:func:`_row_layout`)."""
    order = np.lexsort((col, row, lane))
    lane, row, col, val = lane[order], row[order], col[order], val[order]
    slot = lane * n_rows + row
    nnz = np.bincount(slot, minlength=S * n_rows)
    pos = np.arange(len(slot)) - (np.cumsum(nnz) - nnz)[slot]
    k = int(_row_layout(nnz, _pow2(n_rows))[0])
    idx = np.zeros((S, n_rows, k), dtype=np.int32)
    vals = np.zeros((S, n_rows, k), dtype=np.float64)
    idx[lane, row, pos] = col
    vals[lane, row, pos] = val
    return idx, vals


def _row_layout(nnz, n_pad):
    """``(k, dense)`` of a guest whose rows hold ``nnz`` nonzeros (last
    axis; leading axes are separate guests, each padded to ``n_pad``
    rows): the CSR row width ``k`` — the widest row rounded up to a
    multiple of 4 (tight enough that padded gathers stay cheap, coarse
    enough that compile-cache keys rarely vary), at most ``n_pad`` — and
    whether the refine takes the dense ``swap_select`` branch instead:
    rows over half full and wider than 8."""
    widest = np.maximum(1, np.max(nnz, axis=-1, initial=0))
    k = np.minimum(n_pad, (widest + 3) & ~3)
    return k, k > np.maximum(8, n_pad // 2)


def _sym_host(D: np.ndarray) -> np.ndarray:
    """0.5*(D + D.T), cached by identity — the symmetrised route-weight
    view every gathered-distance expression in the NumPy kernel uses."""
    return _SYM_CACHE.get(
        D, lambda: 0.5 * (np.asarray(D, np.float64)
                          + np.asarray(D, np.float64).T))


def _be():
    be = _backend.active()
    if not getattr(be, "is_jax", False):   # direct calls outside dispatch
        be = _backend.get_backend("jax")
    return be


def _pad_placements(placements: np.ndarray) -> tuple[np.ndarray, int, int]:
    """(B, n) -> zero-padded (B_pad?, n_pad) int32 plus original n."""
    P = np.asarray(placements, dtype=np.int32)
    B, n = P.shape
    n_pad = _pow2(n)
    if n_pad != n:
        P = np.pad(P, ((0, 0), (0, n_pad - n)))
    return P, n, n_pad


# --------------------------------------------------------------------------
# pairwise-swap refinement (the swap-gain kernel)
# --------------------------------------------------------------------------

def _refine_one(p0, idx, val, G_dense, Ds, n_valid, *, movers: int,
                total_passes: int, dense: bool, dims=None,
                scale: float = 1.0, sortless: bool = False):
    """Refine ONE placement; decision-identical to the NumPy loop.

    ``p0`` (n,) int32 node ids (tail >= n_valid is masked padding),
    ``idx``/``val`` (n, k) CSR-padded guest rows, ``G_dense`` (n, n) or
    a (1, 1) placeholder when the sparse path runs, ``Ds`` (N, N)
    symmetrised device-resident distances — or, with static ``dims``
    set (implicit mode), the coordinate and penalty tables from which
    every distance below is computed in-kernel (see :func:`_dist_fns`),
    ``n_valid`` traced scalar.
    """
    n = p0.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    valid = rows < n_valid
    fdt = (Ds[0] if isinstance(Ds, tuple) else Ds).dtype
    dist_pairs, dist_row = _dist_fns(Ds, dims, scale)

    if dims is None:
        M0 = dist_pairs(p0[:, None], p0[None, :])           # (n, n) gather
    else:
        # all-pairs block build — the Pallas torus_hop kernel on TPU
        M0 = dist_pairs.all_pairs(p0, p0).astype(fdt)
    contrib0 = (val.astype(fdt)
                * jnp.take_along_axis(M0, idx, axis=1)).sum(-1)

    def select_mover(M, contrib, i):
        """(best gain, partner j) for mover ``i`` — the fused
        gains-row + masked-argmax + accept step.  ``j == i`` encodes a
        rejected mover (identity swap).  The dense branch is a single
        kernel (:func:`repro.kernels.swap_gain.ops.swap_select`) so the
        gains row never leaves it; the sparse branch applies the same
        mask/argmax/threshold to the CSR-gathered row."""
        if dense:
            from repro.kernels.swap_gain.ops import swap_select
            return swap_select(M, G_dense, contrib, i, n_valid)
        # M is kept exactly symmetric, so every column read below is
        # a (contiguous) row read instead
        idx_i, val_i = idx[i], val[i].astype(fdt)
        Mrow_i = M[i]
        # full-precision matvec: TPU's default f32 dot is one bf16 pass,
        # which would round guest byte counts of ~1e7
        a = jnp.dot(val_i, M[idx_i, :],
                    precision=lax.Precision.HIGHEST)     # M @ G[i]
        b = (val.astype(fdt)
             * Mrow_i[idx]).sum(-1)                      # G @ M[i]
        Ci = jnp.zeros(n, fdt).at[idx_i].add(val_i * Mrow_i[idx_i])
        g = contrib[i] + contrib - 2.0 * Ci - a - b
        g = g.at[i].set(0.0)
        g = jnp.where(valid, g, -jnp.inf)
        j_raw = jnp.argmax(g)
        gain = g[j_raw]
        j = jnp.where((gain > GAIN_EPS) & (i < n_valid), j_raw, i)
        return gain, j.astype(jnp.int32)

    def sparse_col(i):
        """Nonzero structure of G[:, i] (symmetric guest): row i's."""
        return idx[i], val[i].astype(fdt)

    def mover_step(t, s):
        p, M, contrib, improved, order = s
        i = order[t]
        # rejected movers arrive as an *identity swap* (j == i): the M
        # updates below then rewrite rows with their current exact
        # values, so no O(n^2) masked select of M is ever needed and XLA
        # keeps the loop-carried matrix in place.
        gain, j = select_mover(M, contrib, i)
        do = (i < n_valid) & (gain > GAIN_EPS)

        oi, oj = p[i], p[j]
        p_old = p
        p = p.at[jnp.stack([i, j])].set(jnp.stack([oj, oi]))
        # every M entry is a directly gathered Ds value (never
        # accumulated), so the pre-swap rows are re-gathered from Ds
        # instead of read out of M — M stays *write-only* in this
        # section, which is what lets XLA update it in place rather than
        # copying the matrix once per mover
        row_i = dist_row(oj, p)                  # gathered_row(p[i])
        row_j = dist_row(oi, p)
        M = (M.at[i, :].set(row_i).at[:, i].set(row_i)
              .at[j, :].set(row_j).at[:, j].set(row_j))
        M = M.at[jnp.stack([i, j]), jnp.stack([j, i])].set(
            jnp.stack([row_i[j], row_i[j]]))
        if dense:
            old_row_i = dist_row(oi, p_old)
            old_row_j = dist_row(oj, p_old)
            c1 = contrib + (G_dense[i] * (row_i - old_row_i)
                            + G_dense[j] * (row_j - old_row_j))
            c1 = c1.at[i].set((G_dense[i] * row_i).sum())
            c1 = c1.at[j].set((G_dense[j] * row_j).sum())
        else:
            ii, vi = sparse_col(i)
            ij_, vj = sparse_col(j)
            # the sparse delta only needs the old rows at the k nonzero
            # columns — gather those few entries instead of full rows
            old_i_k = dist_row(oi, p_old[ii])
            old_j_k = dist_row(oj, p_old[ij_])
            # delta built separately then added, matching the NumPy
            # fused-expression summation order bit for bit
            delta = jnp.zeros(n, fdt).at[ii].add(vi * (row_i[ii]
                                                       - old_i_k))
            delta = delta.at[ij_].add(vj * (row_j[ij_] - old_j_k))
            c1 = contrib + delta
            c1 = c1.at[jnp.stack([i, j])].set(
                jnp.stack([(vi * row_i[ii]).sum(),
                           (vj * row_j[ij_]).sum()]))
        # contrib accumulates across swaps, so a rejected mover must keep
        # the accumulated values exactly — an O(n) select, unlike M
        contrib = jnp.where(do, c1, contrib)
        return p, M, contrib, improved | do, order

    def pass_body(state):
        p, M, contrib, stop, t = state
        key = jnp.where(valid, contrib, -jnp.inf)
        if sortless:
            # Stable descending argsort WITHOUT the ``sort`` HLO: rank
            # each entry by pairwise comparison (ties broken by index,
            # exactly ``np.argsort(-key, kind="stable")``) and scatter
            # the identity through the rank permutation.  The sharded
            # executables need this: XLA:CPU's SPMD partitioner wraps the
            # ``sort`` primitive inside a shard_map body in channel-
            # tagged AllReduces even though the op is lane-local, which
            # deadlocks its rendezvous and corrupts non-zero ranks.
            # Every other primitive in this loop partitions cleanly, so
            # only the sort is rewritten; the O(n^2) comparison block is
            # cheap at the (<= a few k procs) sizes refine runs at.
            beats = ((key[None, :] > key[:, None])
                     | ((key[None, :] == key[:, None])
                        & (rows[None, :] < rows[:, None])))
            rank = jnp.sum(beats, axis=1, dtype=jnp.int32)
            order = (jnp.zeros(n, jnp.int32).at[rank].set(rows))[:movers]
        else:
            # index tie-break folded into the comparison (two-key sort)
            # rather than ``is_stable`` alone: a unique total order keeps
            # any lowering bit-identical to the NumPy reference
            _, order = lax.sort((-key, rows), num_keys=2)
            order = order[:movers].astype(jnp.int32)
        p, M, contrib, improved, _ = lax.fori_loop(
            0, movers, mover_step, (p, M, contrib, jnp.bool_(False), order))
        return p, M, contrib, ~improved, t + 1

    def cond(state):
        _, _, _, stop, t = state
        return (t < total_passes) & ~stop

    p, _, _, _, _ = lax.while_loop(
        cond, pass_body, (p0, M0, contrib0, jnp.bool_(False),
                          jnp.int32(0)))
    return p


@functools.lru_cache(maxsize=32)
def _refine_jit(movers: int, total_passes: int, dense: bool,
                dims=None, scale: float = 1.0):
    fn = functools.partial(_refine_one, movers=movers,
                           total_passes=total_passes, dense=dense,
                           dims=dims, scale=scale)
    batched = jax.vmap(fn, in_axes=(0, None, None, None, None, None))
    return jax.jit(batched)


@functools.lru_cache(maxsize=8)
def _mesh(n_dev: int):
    """One cached 1-D device mesh per device count, shared between the
    shard_map trace and the explicit operand placement in
    :func:`refine_many` (the same mesh object must back both)."""
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n_dev]), ("dev",))


@functools.lru_cache(maxsize=32)
def _refine_jit_sharded(movers: int, total_passes: int, dense: bool,
                        dims, scale: float, n_dev: int):
    """Candidate-stack refine sharded over ``n_dev`` devices.

    ``shard_map`` splits the (B, n) placement stack along the candidate
    axis — guest structure and distances are replicated — so each device
    vmaps only its B/n_dev slice, and each shard's ``lax.while_loop``
    stops as soon as *its own* candidates converge (the single-device
    vmap runs every pass until the slowest candidate in the whole stack
    converges).  Candidates never interact, so the result is
    bit-identical to the single-device dispatch in any shard order.

    Callers must hand in operands **already placed** on this mesh
    (stack sharded over ``"dev"``, everything else replicated — see
    ``_shard_args``): letting jit reshard single-device-committed inputs
    makes XLA:CPU synthesise cross-module collectives, which both
    deadlock its rendezvous under concurrent dispatches and mis-replicate
    on sub-meshes.
    """
    from jax.sharding import PartitionSpec as P
    fn = functools.partial(_refine_one, movers=movers,
                           total_passes=total_passes, dense=dense,
                           dims=dims, scale=scale, sortless=True)
    batched = jax.vmap(fn, in_axes=(0, None, None, None, None, None))
    sharded = jax.shard_map(batched, mesh=_mesh(n_dev),
                            in_specs=(P("dev"), P(), P(), P(), P(), P()),
                            out_specs=P("dev"), check_vma=False)
    return jax.jit(sharded)


def _shard_args(n_dev: int, P_stack, *replicated):
    """Place the candidate stack sharded over the mesh's ``dev`` axis and
    every other operand fully replicated, so the jitted shard_map never
    has to reshard committed single-device arrays itself.

    Replication is routed through the **host**: ``device_put`` of an
    array already committed to one device compiles a device-to-device
    broadcast, which XLA:CPU emits as a cross-module AllReduce that both
    deadlocks its rendezvous and hands corrupted replicas to non-zero
    ranks (deterministically wrong lanes).  A host ``np.ndarray`` takes
    the plain host-to-each-device copy path instead, which is collective
    free."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh(n_dev)
    shard = NamedSharding(mesh, P("dev"))
    rep = NamedSharding(mesh, P())
    out = [jax.device_put(np.asarray(P_stack), shard)]
    out.extend(jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), rep), arg)
        for arg in replicated)
    return out


def _device_distances(D, be):
    """``(device operand, static spec key, scale)`` — the dense
    symmetrised matrix (key ``None``), the ``(coords, penalised)`` pair
    with static torus ``dims``, or the ``(coords, penalty)`` pair keyed
    ``("fattree",)`` in fat-tree implicit mode."""
    spec = getattr(D, "implicit", None)
    if spec is None:
        return be.device_matrix(_sym_host(D)), None, 1.0
    if getattr(spec, "kind", "torus") == "fattree":
        operand = (be.device_matrix(spec.coords),
                   be.device_matrix(spec.penalty))
        return operand, ("fattree",), float(spec.scale)
    operand = (be.device_matrix(spec.coords),
               be.device_matrix(spec.penalised))
    return operand, spec.dims, float(spec.scale)


def refine_many(G_w: np.ndarray, D: np.ndarray, placements: np.ndarray,
                *, lane=None) -> np.ndarray:
    """Batched ``_pairwise_refine``: (B, n) placements in one dispatch.

    With multiple visible devices (``backend.JaxBackend.device_count``
    > 1, e.g. under ``XLA_FLAGS=--xla_force_host_platform_device_count``
    or a real multi-chip topology) the candidate stack is sharded across
    them; the batch axis is padded to a device multiple by repeating the
    last candidate (refinement is deterministic per candidate, so the
    duplicates are free of side effects and sliced off).

    Spans (:mod:`repro.core.spans`): ``refine`` (args ``B``, ``n``) is
    split into ``refine.prepare`` (host work up to the enqueue: padding,
    sparse rows, device copies, the launch) and ``refine.wait`` (the
    device's work and the copy back to the host).

    ``lane=(batch, s)`` asks for sub-problem ``s`` of a
    :class:`SubproblemBatch` (``G_w`` and ``placements`` are then that
    sub-problem's guest and its one-row placement): the batch's one
    dispatch, under span ``refine.subproblems`` (args ``S`` sub-problems,
    ``n`` padded width) with the same ``refine.prepare`` /
    ``refine.wait`` split, is made by the first of its lanes asked for.
    """
    if lane is not None:
        batch, s = lane
        out = batch.result(s, placements)
        return out[None] if np.asarray(placements).ndim == 2 else out
    be = _be()
    B, n = np.atleast_2d(placements).shape
    with _spans.span("refine", B=B, n=n):
        with be.scope(), _spans.span("refine.prepare"):
            run, args, n_dev = refine_program(G_w, D, placements)
            if n_dev > 1:
                be.stats["sharded_dispatches"] += 1
            out = run(*args)
        with _spans.span("refine.wait"):
            out = np.asarray(out)[:B, :n].astype(np.int64)
    return out if np.asarray(placements).ndim == 2 else out[0]


def refine_program(G_w: np.ndarray, D, placements: np.ndarray):
    """``(jitted refine, operands, n_dev)``: the dispatch
    :func:`refine_many` makes for these arguments, on ``n_dev`` devices.
    ``run.lower(*operands)`` shows the program it compiles.  Call inside
    the active backend's :meth:`~repro.core.backend.JaxBackend.scope`."""
    be = _be()
    P, n, n_pad = _pad_placements(np.atleast_2d(placements))
    idx, val, G_dense, dense = _guest_device(G_w, n_pad, be)
    Ds, dims, scale = _device_distances(D, be)
    movers = min(REFINE_MOVERS, n_pad)
    B = P.shape[0]
    n_dev = min(int(be.device_count), B)
    if n_dev > 1:
        pad_b = (-B) % n_dev
        if pad_b:
            P = np.pad(P, ((0, pad_b), (0, 0)), mode="edge")
        run = _refine_jit_sharded(movers, REFINE_PASSES, dense, dims,
                                  scale, n_dev)
        args = _shard_args(n_dev, P, idx, val, G_dense, Ds, jnp.int32(n))
    else:
        run = _refine_jit(movers, REFINE_PASSES, dense, dims, scale)
        args = (jnp.asarray(P), idx, val, G_dense, Ds, jnp.int32(n))
    return run, args, n_dev


def _guest_device(G_w: np.ndarray, n_pad: int, be):
    """Device-resident guest structure (idx, val, G_dense, is_dense),
    cached by guest identity so repeated refine/score calls against one
    job's graph pay a single transfer."""
    def build():
        idx, val, _, _G = _sparse_rows(G_w)
        n = idx.shape[0]
        if n_pad != n:
            idx = np.pad(idx, ((0, n_pad - n), (0, 0)))
            val = np.pad(val, ((0, n_pad - n), (0, 0)))
        _, dense = _row_layout(np.count_nonzero(_G, axis=1), n_pad)
        fdt = be.np_dtype
        Gd = _G
        if dense and n_pad != n:
            Gd = np.pad(Gd, ((0, n_pad - n), (0, n_pad - n)))
        G_dense = (jnp.asarray(Gd, dtype=fdt) if dense
                   else jnp.zeros((1, 1), dtype=fdt))
        return (jnp.asarray(idx), jnp.asarray(val, dtype=fdt),
                G_dense, dense)
    key_holder = _sparse_rows(G_w)    # one entry per guest object
    cache = _SPARSE_DEV_CACHE.get(key_holder, dict)
    sub = (n_pad, be.dtype)
    if sub not in cache:
        cache[sub] = build()
    return cache[sub]


# --------------------------------------------------------------------------
# disjoint sub-problems of one guest (a multilevel uncoarsening level)
# --------------------------------------------------------------------------

_COO_CACHE = _IdLRU()


def _guest_coo(G_w: np.ndarray):
    """Off-diagonal nonzeros of the guest, row-major: (rows, cols, vals).
    Cached by identity — every candidate of a placement refines its
    levels against the same guest object."""
    def build():
        G = np.asarray(G_w, dtype=np.float64)
        r, c = np.nonzero(G)
        off = r != c
        r, c = r[off], c[off]
        return r, c, G[r, c]
    return _COO_CACHE.get(G_w, build)


@functools.lru_cache(maxsize=32)
def _refine_jit_subproblems(movers: int, total_passes: int, dims=None,
                            scale: float = 1.0):
    """Sparse refine of a stack of *different* guests: placement, CSR
    rows and valid count per lane, the distance operand shared."""
    fn = functools.partial(_refine_one, movers=movers,
                           total_passes=total_passes, dense=False,
                           dims=dims, scale=scale)
    return jax.jit(jax.vmap(fn, in_axes=(0, 0, 0, None, None, 0)))


class SubproblemBatch:
    """Disjoint sub-problems of one guest, refined in one device dispatch.

    Sub-problem ``s`` is the guest restricted to processes ``groups[s]``
    (``G_w[np.ix_(F, F)]``), placed at ``placements[s]``.  Its result is
    asked for through :func:`refine_many` with ``lane=(batch, s)``; the
    first such call refines every sub-problem at once, as the lanes of
    one vmapped program, and later calls return their lane.  Each lane
    is padded to one width (the power of two of the largest sub-problem)
    and one sparse row width, and the stack to a power of two by
    repeating its last lane (sliced off), so a few compiled programs
    serve every level.  Padding rows are masked, padded movers are
    identity swaps, and a converged lane of the vmapped loop is kept as
    it is, so each lane accepts the swaps it would accept alone.  Runs
    on one device.

    ``dense`` is whether some sub-problem, refined alone, would take the
    dense ``swap_select`` branch; such a batch cannot run, and each
    sub-problem takes its own :func:`refine_many` dispatch instead.
    """

    def __init__(self, G_w: np.ndarray, D, groups, placements):
        self.D = D
        self.inputs = [np.asarray(p, dtype=np.int64) for p in placements]
        self.sizes = np.array([len(F) for F in groups], dtype=np.int64)
        self.n_pad = _pow2(int(self.sizes.max()))
        S = len(groups)
        flat = np.concatenate(groups).astype(np.int64)
        lanes = np.repeat(np.arange(S), self.sizes)
        lane_of = np.full(G_w.shape[0], -1, dtype=np.int64)
        lane_of[flat] = lanes
        if not np.array_equal(lane_of[flat], lanes):
            raise ValueError("sub-problem process groups must be disjoint")
        loc = np.zeros(G_w.shape[0], dtype=np.int64)
        loc[flat] = (np.arange(len(flat))
                     - np.repeat(np.cumsum(self.sizes) - self.sizes,
                                 self.sizes))
        r, c, v = _guest_coo(G_w)
        keep = (lane_of[r] >= 0) & (lane_of[r] == lane_of[c])
        self._entries = (lane_of[r[keep]], loc[r[keep]], loc[c[keep]],
                         v[keep])
        # the dense test each sub-problem would get alone
        lane, row = self._entries[:2]
        nnz = np.bincount(lane * self.n_pad + row,
                          minlength=S * self.n_pad).reshape(S, self.n_pad)
        own_pad = np.array([_pow2(n) for n in self.sizes])
        self.dense = bool(_row_layout(nnz, own_pad)[1].any())
        self._out = None

    def program(self):
        """``(jitted refine, operands)``: the one dispatch of this batch.
        Call inside the active backend's
        :meth:`~repro.core.backend.JaxBackend.scope`."""
        if self.dense:
            raise ValueError("a sub-problem takes the dense branch alone")
        be = _be()
        S, n_pad = len(self.sizes), self.n_pad
        lanes = np.repeat(np.arange(S), self.sizes)
        local = np.concatenate([np.arange(n) for n in self.sizes])
        P = np.zeros((S, n_pad), dtype=np.int32)
        P[lanes, local] = np.concatenate(self.inputs)
        idx, val = _csr_rows(*self._entries, S, n_pad)
        n_valid = self.sizes.astype(np.int32)
        S_pad = _pow2(S)
        if S_pad != S:
            P, idx, val, n_valid = (
                np.pad(a, ((0, S_pad - S),) + ((0, 0),) * (a.ndim - 1),
                       mode="edge") for a in (P, idx, val, n_valid))
        Ds, dims, scale = _device_distances(self.D, be)
        run = _refine_jit_subproblems(min(REFINE_MOVERS, n_pad),
                                      REFINE_PASSES, dims, scale)
        P, idx, val, n_valid = jax.device_put(
            (P, idx, val.astype(be.np_dtype), n_valid))
        return run, (P, idx, val, jnp.zeros((1, 1), be.np_dtype), Ds,
                     n_valid)

    def result(self, s: int, placement: np.ndarray) -> np.ndarray:
        """Lane ``s`` refined, dispatching the batch on first demand;
        ``placement`` must be the lane's input."""
        if not np.array_equal(np.ravel(placement), self.inputs[s]):
            raise ValueError(f"lane {s} was batched with another placement")
        if self._out is None:
            be = _be()
            with _spans.span("refine.subproblems", S=len(self.sizes),
                             n=self.n_pad):
                with be.scope(), _spans.span("refine.prepare"):
                    run, args = self.program()
                    out = run(*args)
                with _spans.span("refine.wait"):
                    self._out = np.asarray(out).astype(np.int64)
        return self._out[s, :self.sizes[s]].copy()


def refine_subproblems(G_w: np.ndarray, D, groups,
                       placements) -> list[np.ndarray]:
    """Refine disjoint sub-problems of one guest, all in one dispatch.

    Each sub-problem goes through :func:`refine_many` on its own guest
    ``G_w[np.ix_(F, F)]`` and its one-row placement, so a wrapper of
    ``refine_many`` sees every sub-problem as before, and each result is
    what ``refine_many`` would return for it alone; the calls share one
    :class:`SubproblemBatch`, which makes the only device dispatch.
    Where a sub-problem alone would take the dense branch, each call is
    its own dispatch instead.
    """
    if not len(groups):
        return []
    batch = SubproblemBatch(G_w, D, groups, placements)
    return [refine_many(G_w[np.ix_(F, F)], D, p[None],
                        lane=None if batch.dense else (batch, s))[0]
            for s, (F, p) in enumerate(zip(groups, batch.inputs))]


# --------------------------------------------------------------------------
# hop-bytes scoring
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _hop_bytes_jit(dims=None, scale: float = 1.0):
    def score(P, idx, val, Ds, n_valid):
        dist_pairs, _ = _dist_fns(Ds, dims, scale)

        def one(p):
            tgt = p[idx]                       # (n, k) partner node ids
            d = dist_pairs(p[:, None], tgt)    # gathered / in-kernel
            ok = jnp.arange(p.shape[0])[:, None] < n_valid
            return 0.5 * jnp.where(ok, val * d.astype(val.dtype), 0.0).sum()
        return jax.vmap(one)(P)
    return jax.jit(score)


def hop_bytes_batch(G_w: np.ndarray, D: np.ndarray,
                    placements: np.ndarray) -> np.ndarray:
    """Batched hop-bytes on device; bit-equal to the NumPy gather."""
    be = _be()
    P2 = np.atleast_2d(np.asarray(placements))
    P, n, n_pad = _pad_placements(P2)
    with be.scope():
        idx, val, _Gd, _dense = _guest_device(G_w, n_pad, be)
        Ds, dims, scale = _device_distances(D, be)
        out = _hop_bytes_jit(dims, scale)(
            jnp.asarray(P), idx, val, Ds, jnp.int32(n))
    return np.asarray(out, dtype=np.float64)


# --------------------------------------------------------------------------
# node-subset selection (frontier growth)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _select_jit():
    def grow(Ddev, seed, count):
        N = Ddev.shape[0]
        chosen0 = jnp.zeros(N, bool).at[seed].set(True)
        cost0 = Ddev[seed].at[seed].set(jnp.inf)

        def step(_, s):
            chosen, cost = s
            nxt = jnp.argmin(cost)
            return chosen.at[nxt].set(True), (cost + Ddev[nxt]).at[nxt].set(
                jnp.inf)

        chosen, _ = lax.fori_loop(0, count - 1, step, (chosen0, cost0))
        return chosen
    return jax.jit(grow)


def select_nodes(D: np.ndarray, count: int,
                 seed: int | None = None) -> np.ndarray:
    """Drop-in for :func:`repro.core.mapping.select_nodes` — the O(N^2)
    seed search stays on host (one partition, same arithmetic as NumPy);
    the sequential frontier growth runs jitted on device."""
    n = D.shape[0]
    count = min(count, n)
    if seed is None:
        part = np.partition(D, count - 1, axis=1)[:, :count]
        seed = int(np.argmin(part.sum(axis=1)))
    be = _be()
    with be.scope():
        Ddev = be.device_matrix(np.asarray(D, dtype=np.float64))
        chosen = _select_jit()(Ddev, jnp.int32(seed), jnp.int32(count))
    return np.flatnonzero(np.asarray(chosen)).astype(np.int64)


# --------------------------------------------------------------------------
# greedy pair placement (paper baseline)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _greedy_jit():
    def run(pair_i, pair_j, pair_ok, Ddev, free0, placement0):
        def nearest_free(free, anchor):
            return jnp.argmin(jnp.where(free, Ddev[anchor], jnp.inf))

        def step(t, s):
            placement, free = s
            i, j, ok = pair_i[t], pair_j[t], pair_ok[t]
            pi, pj = placement[i], placement[j]

            def both(args):
                placement, free = args
                a = jnp.argmax(free)                  # first free id
                free = free.at[a].set(False)
                b = nearest_free(free, a)
                free = free.at[b].set(False)
                return (placement.at[i].set(a.astype(jnp.int32))
                        .at[j].set(b.astype(jnp.int32)), free)

            def only_i(args):
                placement, free = args
                a = nearest_free(free, pj)
                return (placement.at[i].set(a.astype(jnp.int32)),
                        free.at[a].set(False))

            def only_j(args):
                placement, free = args
                b = nearest_free(free, pi)
                return (placement.at[j].set(b.astype(jnp.int32)),
                        free.at[b].set(False))

            def nothing(args):
                return args

            case = jnp.where(
                ~ok | ((pi >= 0) & (pj >= 0)), 0,
                jnp.where((pi < 0) & (pj < 0), 1,
                          jnp.where(pi < 0, 2, 3)))
            return lax.switch(case, [nothing, both, only_i, only_j],
                              (placement, free))

        return lax.fori_loop(0, pair_i.shape[0], step, (placement0, free0))
    return jax.jit(run)


def greedy_placement(G_w: np.ndarray, nodes: np.ndarray,
                     D: np.ndarray) -> np.ndarray:
    """Drop-in for :func:`repro.core.mapping.greedy_placement`: the
    traffic-sorted pair list is built on host (identical ordering), the
    frontier loop runs jitted against the device-resident distances."""
    n = G_w.shape[0]
    nodes = np.asarray(nodes)
    iu = np.triu_indices(n, 1)
    w = np.asarray(G_w)[iu]
    order = np.argsort(-w, kind="stable")
    order = order[w[order] > 0]
    m = len(order)
    m_pad = _pow2(max(1, m))
    pair_i = np.zeros(m_pad, dtype=np.int32)
    pair_j = np.zeros(m_pad, dtype=np.int32)
    pair_ok = np.zeros(m_pad, dtype=bool)
    pair_i[:m] = iu[0][order]
    pair_j[:m] = iu[1][order]
    pair_ok[:m] = True

    be = _be()
    free0 = np.zeros(D.shape[0], dtype=bool)
    free0[np.unique(nodes)] = True
    with be.scope():
        Ddev = be.device_matrix(np.asarray(D, dtype=np.float64))
        placement, free = _greedy_jit()(
            jnp.asarray(pair_i), jnp.asarray(pair_j), jnp.asarray(pair_ok),
            Ddev, jnp.asarray(free0), jnp.full(n, -1, dtype=jnp.int32))
    placement = np.asarray(placement).astype(np.int64)
    free_ids = np.flatnonzero(np.asarray(free))
    rem = np.flatnonzero(placement < 0)
    placement[rem] = free_ids[:len(rem)]
    return placement
