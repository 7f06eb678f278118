"""A torus fabric (``{"kind": "torus", "dims": [...]}``), by node id.

``Reference`` is the plain float64 Eq. (1) of the paper and imports
nothing of the program: a route is dimension-ordered, shortest wrap per
dimension, ties toward +1, and costs one per hop plus 100 per link that
touches a penalised node.  ``topology`` builds the program's own object
for the same fabric.
"""
from __future__ import annotations

import numpy as np

FAULT_PENALTY = 100.0


class Reference:
    def __init__(self, spec: dict):
        self.dims = tuple(int(d) for d in spec["dims"])
        self.n_nodes = int(np.prod(self.dims))

    def coords(self, ids) -> np.ndarray:
        """(..., ndim) row-major coordinates."""
        out, rest = [], np.asarray(ids, dtype=np.int64)
        for d in reversed(self.dims):
            out.append(rest % d)
            rest = rest // d
        return np.stack(out[::-1], axis=-1)

    def node_at(self, coords: np.ndarray) -> np.ndarray:
        node = np.zeros(coords.shape[:-1], dtype=np.int64)
        for j, d in enumerate(self.dims):
            node = node * d + coords[..., j] % d
        return node

    def weights(self, u, v, penal: np.ndarray) -> np.ndarray:
        """Eq. (1) weight of the route u -> v, broadcast over u and v,
        walking every route one hop at a time, all pairs at once.
        ``penal`` is an (n_nodes,) bool mask of penalised nodes."""
        u, v = np.broadcast_arrays(np.asarray(u, np.int64),
                                   np.asarray(v, np.int64))
        cur = self.coords(u).copy()
        dst = self.coords(v)
        prev_bad = penal[u]
        hops = np.zeros(u.shape)
        bad_links = np.zeros(u.shape)
        for j, d in enumerate(self.dims):
            fwd = (dst[..., j] - cur[..., j]) % d
            bwd = (cur[..., j] - dst[..., j]) % d
            step = np.where(fwd <= bwd, 1, -1)
            left = np.minimum(fwd, bwd)
            for _ in range(max(d // 2, 1)):
                moving = left > 0
                if not moving.any():
                    break
                cur[..., j] = np.where(moving, (cur[..., j] + step) % d,
                                       cur[..., j])
                node = self.node_at(cur)
                bad = penal[node]
                hops += moving
                bad_links += moving & (prev_bad | bad)
                prev_bad = np.where(moving, bad, prev_bad)
                left = left - moving
        return hops + FAULT_PENALTY * bad_links


def topology(spec: dict):
    """The program's topology object for this fabric."""
    from repro.core.topology import TorusTopology
    return TorusTopology(tuple(int(d) for d in spec["dims"]))
