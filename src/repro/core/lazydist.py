"""LazyDistance — numpy-indexable implicit distance matrices, O(N) memory.

Above the engine's size threshold, :meth:`TorusTopology.lazy_distance` /
:meth:`FatTreeTopology.lazy_distance` hand the mapping pipeline one of
these adapters instead of a dense (N, N) matrix.  Every indexing idiom
the hot kernels use —

    D[i]                     row            (N,)
    D[rows]                  row block      (len(rows), N)
    D[i, j] / D[i, cols]     elementwise
    D[np.ix_(rows, cols)]    open-mesh block
    D[P[:, :, None], P[:, None, :]]   broadcast fancy (hop_bytes_batch)

— is computed on demand from the coordinate table in O(#requested
elements) memory, bit-identical to the entries the topology's dense
``weight_matrix`` would hold (differentially asserted in
``tests/test_multilevel.py``).  ``np.asarray(D)`` raises: nothing in the
pipeline may silently densify the matrix.

Fault/straggler weighting stays **exact**, not approximate.  For the
torus, the Eq. (1) extra terms are nonzero only for pairs whose
dimension-ordered route touches a penalised node; the adapter flags
candidate pairs with vectorized route-membership conditions (a node is
on the dimension-ordered route when, for some dimension k, its prefix
matches v, its suffix matches u, and its k-th coordinate lies on the
shortest wrap path) and prices the flagged pairs with one vectorised
route walk (:meth:`TorusTopology.route_extras`, the walk of the dense
``weight_matrix``), in the program span ``distance.extras``.
Fat-tree weighting is endpoint-form and trivially elementwise.

Both adapters also expose an ``implicit`` spec (coordinates + metric
kind + scale + the health state's penalty data) that lets the jax
backend compute distances in-kernel (:mod:`repro.kernels.hop_dist`)
instead of going through ``__getitem__`` at all: the fat-tree in every
health state (a per-endpoint penalty vector), the torus in every health
state without stragglers (the table of penalised coordinates, from
which the kernel counts a route's penalised links in closed form).  A
straggling torus prices links by slowdown, which the kernel does not
model: its ``implicit`` is ``None`` and the NumPy kernels serve it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.kernels.hop_dist.ops import torus_hop_np

from . import spans as _spans


@dataclasses.dataclass(frozen=True)
class ImplicitSpec:
    """What the jax backend needs to compute distances in-kernel:
    per-node integer coordinates, a static metric spec, a uniform scale,
    and the health state's penalty data.

    ``kind="torus"`` interprets ``coords`` against wraparound ``dims``
    and carries ``penalised``, the (ndim, F) table of the penalised
    nodes' coordinates, padded to a power of two F with -1 (F = 0 when
    healthy); ``kind="fattree"`` interprets them as (pod, edge, host)
    triples with ``dims=()`` and carries the per-node endpoint
    ``penalty`` vector (zeros when healthy).  Both are always present,
    so the backend's identity-keyed device-transfer cache has a stable
    array to pin.
    """

    coords: np.ndarray          # (N, ndim) float64 — stable identity for
                                # the backend's device-transfer cache
    dims: tuple[int, ...]
    scale: float
    kind: str = "torus"
    penalty: Optional[np.ndarray] = None    # (N,) float64, fat-tree only
    penalised: Optional[np.ndarray] = None  # (ndim, F) float64, torus only


class LazyDistance:
    """Base adapter: numpy-compatible read-only 2-D indexing over an
    implicit distance function."""

    ndim = 2
    dtype = np.dtype(np.float64)

    def __init__(self, n: int):
        self.shape = (n, n)

    # ---- subclass hook -------------------------------------------------
    def _elems(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Entries D[u, v] for same-shape int arrays ``u``, ``v``."""
        raise NotImplementedError

    # ---- numpy protocol ------------------------------------------------
    def __array__(self, dtype=None, copy=None):
        raise TypeError(
            f"refusing to densify a {type(self).__name__} of shape "
            f"{self.shape} — index it (rows / pairs / np.ix_ blocks) "
            f"instead, or use the topology's dense weight_matrix() below "
            f"the lazy threshold")

    def __len__(self) -> int:
        return self.shape[0]

    @property
    def implicit(self) -> Optional[ImplicitSpec]:
        """In-kernel computation spec, or None when only ``__getitem__``
        applies (a straggling torus)."""
        return None

    def _axis(self, key, n: int) -> np.ndarray:
        if isinstance(key, slice):
            return np.arange(*key.indices(n))
        a = np.asarray(key)
        if a.dtype == bool:
            a = np.flatnonzero(a)
        return a.astype(np.int64, copy=False)

    def __getitem__(self, key):
        n = self.shape[0]
        if isinstance(key, tuple):
            if len(key) != 2:
                raise IndexError(
                    f"{type(self).__name__} supports 2-d indexing only")
            u, v = (self._axis(key[0], n), self._axis(key[1], n))
            both_scalar = u.ndim == 0 and v.ndim == 0
            u, v = np.broadcast_arrays(u, v)
            out = self._elems(u, v)
            return float(out) if both_scalar else out
        rows = self._axis(key, n)
        cols = np.arange(n, dtype=np.int64)
        u, v = np.broadcast_arrays(rows[..., None], cols)
        return self._elems(u, v)


class TorusLazyDistance(LazyDistance):
    """Implicit Eq. (1) route weights of a :class:`TorusTopology`."""

    def __init__(self, topo, p_f: Optional[np.ndarray] = None,
                 c: float = 1.0, straggler: Optional[np.ndarray] = None):
        super().__init__(topo.n_nodes)
        self.topo = topo
        self.c = float(c)
        self.coords = topo.coords_array().astype(np.int64)
        self.dims = tuple(topo.dims)
        penal = (np.zeros(topo.n_nodes, dtype=bool) if p_f is None
                 else np.asarray(p_f, np.float64) > 0)
        slow = None
        if straggler is not None:
            s = np.asarray(straggler, dtype=np.float64)
            if (s > 0).any():
                slow = s
        self._penal = penal
        self._slow = slow
        slow_mask = np.zeros(topo.n_nodes, bool) if slow is None else slow > 0
        self._interesting = np.flatnonzero(penal | slow_mask)
        self._links = None        # link cost tables, built on first use
        self._spec = None
        if slow is None:
            self._spec = ImplicitSpec(
                coords=self.coords.astype(np.float64),
                dims=self.dims, scale=self.c,
                penalised=penalised_table(self.coords[penal]))

    @property
    def implicit(self) -> Optional[ImplicitSpec]:
        return self._spec

    def _elems(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        cu = self.coords[u]
        cv = self.coords[v]
        out = self.c * torus_hop_np(cu, cv, self.dims)
        if self._interesting.size == 0:
            return out
        flagged = self._on_route_any(u, v, cu, cv)
        if not flagged.any():
            return out
        out = np.ascontiguousarray(out)
        flat = np.flatnonzero(flagged.ravel())
        with _spans.span("distance.extras", pairs=int(u.size),
                         flagged=int(flat.size)):
            out.ravel()[flat] += self._route_extra(u.ravel()[flat],
                                                   v.ravel()[flat])
        return out

    def _on_route_any(self, u, v, cu, cv) -> np.ndarray:
        """Pairs whose dimension-ordered route u -> v touches any
        penalised/straggling node, evaluated per requested pair.

        While the route corrects dimension ``k``, the visited nodes have
        coordinates ``(v[<k], path(u[k] -> v[k]), u[>k])``, so node x is
        on route(u, v) iff for some k the prefix of x matches v, the
        suffix matches u, and ``x[k]`` lies on the shortest wrap path in
        dimension k (ties toward +1, as :meth:`TorusTopology.route`)."""
        ndim = len(self.dims)
        aff = np.zeros(u.shape, dtype=bool)
        for x in self._interesting:
            xc = self.coords[int(x)]
            # u-side suffix match for dims strictly after k
            post = np.ones(u.shape + (ndim + 1,), dtype=bool)
            for j in range(ndim - 1, -1, -1):
                post[..., j] = post[..., j + 1] & (cu[..., j] == xc[j])
            pre = np.ones(u.shape, dtype=bool)   # v-side prefix match
            for k in range(ndim):
                d = self.dims[k]
                a = cu[..., k]
                b = cv[..., k]
                fwd = (b - a) % d
                bwd = (a - b) % d
                on_f = ((xc[k] - a) % d) <= fwd
                on_b = ((a - xc[k]) % d) <= bwd
                on = np.where(fwd <= bwd, on_f, on_b)
                aff |= post[..., k + 1] & pre & on
                pre = pre & (cv[..., k] == xc[k])
        return aff & (u != v)                    # empty routes touch nothing

    def _route_extra(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Exact Eq. (1) extras of the pairs ``u -> v`` (flat id arrays):
        one vectorised walk of their routes that adds the same link costs
        in the same order as :meth:`TorusTopology.weight_matrix`."""
        if self._links is None:
            slow = (np.zeros(self.shape[0]) if self._slow is None
                    else np.where(self._slow > 0, self._slow, 0.0))
            self._links = self.topo.link_costs(self._penal, slow, self.c)
        return self.topo.route_extras(u, v, self._links)


def penalised_table(coords: np.ndarray) -> np.ndarray:
    """(F, ndim) coordinates of the penalised nodes -> the (ndim, F_pad)
    float64 table of :class:`ImplicitSpec`, F_pad the next power of two
    (0 stays 0) and the padding columns -1, which match no node."""
    f, ndim = coords.shape
    width = 0 if f == 0 else 1 << (f - 1).bit_length()
    out = np.full((ndim, width), -1.0)
    out[:, :f] = coords.T
    return out


class FatTreeLazyDistance(LazyDistance):
    """Implicit endpoint-form Eq. (1) weights of a
    :class:`FatTreeTopology` (exact for any health state — paths touch
    compute nodes only at their endpoints).

    Because the fault/straggler weighting is a per-endpoint penalty
    gather — no route walks — the adapter exposes an ``implicit`` spec
    for **every** health state, so the jax backend compiles fat-tree
    distances in-kernel even under faults and stragglers.
    """

    def __init__(self, topo, p_f: Optional[np.ndarray] = None,
                 c: float = 1.0, straggler: Optional[np.ndarray] = None):
        super().__init__(topo.n_nodes)
        self.topo = topo
        self.c = float(c)
        self.coords = topo.coords_array().astype(np.int64)
        from .topology import FAULT_PENALTY
        penalty = np.zeros(topo.n_nodes)
        if p_f is not None:
            penalty += c * FAULT_PENALTY * (np.asarray(p_f, np.float64) > 0)
        if straggler is not None:
            penalty += c * np.asarray(straggler, dtype=np.float64)
        self._penalty = penalty if (penalty > 0).any() else None
        # the zeros vector is kept (not None) so the spec always carries
        # a stable array for the backend's identity-keyed transfer cache
        self._spec = ImplicitSpec(
            coords=self.coords.astype(np.float64), dims=(), scale=self.c,
            kind="fattree", penalty=penalty)

    @property
    def implicit(self) -> Optional[ImplicitSpec]:
        return self._spec

    def _elems(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        from repro.kernels.hop_dist.ops import fattree_hop_np
        out = self.c * fattree_hop_np(self.coords[u], self.coords[v])
        if self._penalty is not None:
            out += np.where(u != v, self._penalty[u] + self._penalty[v], 0.0)
        return out


def is_lazy(D) -> bool:
    """True when ``D`` is a lazy adapter rather than a dense ndarray."""
    return isinstance(D, LazyDistance)
