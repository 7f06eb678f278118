"""A k-ary fat-tree fabric (``{"kind": "fattree", "k": k}``), by host id.

``Reference`` is the plain float64 Eq. (1) in endpoint form and imports
nothing of the program: hosts relay nothing, so a route between hosts
of one edge switch, one pod or two pods costs 2, 4 or 6 hops, plus 100
for each of its two endpoints that is penalised.  ``topology`` builds
the program's own object for the same fabric.
"""
from __future__ import annotations

import numpy as np

FAULT_PENALTY = 100.0


class Reference:
    def __init__(self, spec: dict):
        self.k = int(spec["k"])
        if self.k < 2 or self.k % 2:
            raise ValueError(f"fat-tree arity must be even, got {self.k}")
        self.n_nodes = self.k ** 3 // 4

    def coords(self, ids) -> np.ndarray:
        """(..., 3) coordinates (pod, edge, host)."""
        half = self.k // 2
        pod, rest = np.divmod(np.asarray(ids, dtype=np.int64), half * half)
        edge, host = np.divmod(rest, half)
        return np.stack([pod, edge, host], axis=-1)

    def weights(self, u, v, penal: np.ndarray) -> np.ndarray:
        """Eq. (1) weight of the route u -> v, broadcast over u and v.
        ``penal`` is an (n_nodes,) bool mask of penalised hosts."""
        u, v = np.broadcast_arrays(np.asarray(u, np.int64),
                                   np.asarray(v, np.int64))
        cu, cv = self.coords(u), self.coords(v)
        same_pod = cu[..., 0] == cv[..., 0]
        same_edge = same_pod & (cu[..., 1] == cv[..., 1])
        same_host = same_edge & (cu[..., 2] == cv[..., 2])
        hops = (6.0 - 2.0 * same_pod.astype(np.float64)
                - 2.0 * same_edge - 2.0 * same_host)
        pen = FAULT_PENALTY * penal.astype(np.float64)
        return hops + np.where(u != v, pen[u] + pen[v], 0.0)


def topology(spec: dict):
    """The program's topology object for this fabric."""
    from repro.core.fattree import FatTreeTopology
    return FatTreeTopology(int(spec["k"]))
