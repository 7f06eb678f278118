"""Plain float64 reference of what a placement is judged by.

Everything here is written from the paper's definitions and imports
nothing of the system under test:

* hop-bytes: sum over unordered rank pairs of bytes times route weight.
* the best single swap of two ranks' nodes, and how much it would save.
* Slurm's default block placement: the first n allocatable node ids.

Eq. (1) route weights are each fabric kind's own, in
``fabrics/<kind>.py`` (``Reference.weights``), found by the kind's name.
They are computed for the node pairs asked for, never for the whole
fabric, so a 27,648-host fat-tree costs what its placements touch.
"""
from __future__ import annotations

import numpy as np


def sym_weights(fabric, nodes: np.ndarray,
                penal: np.ndarray) -> np.ndarray:
    """Symmetrised (n, n) route weights among ``nodes``."""
    nodes = np.asarray(nodes, dtype=np.int64)
    W = fabric.weights(nodes[:, None], nodes[None, :], penal)
    return 0.5 * (W + W.T)


def hop_bytes(G: np.ndarray, M: np.ndarray) -> float:
    """Hop-bytes of a placement whose pairwise weights are ``M``."""
    return float(0.5 * (G * M).sum())


def swap_gains(G: np.ndarray, M: np.ndarray) -> np.ndarray:
    """(n, n) hop-bytes saved by swapping the nodes of ranks i and j.

    contrib_i + contrib_j - 2 G_ij M_ij - (GM)_ij - (GM)_ji, with
    ``M`` symmetric and zero on the diagonal."""
    G = np.array(G, dtype=np.float64)
    np.fill_diagonal(G, 0.0)
    contrib = (G * M).sum(axis=1)
    GM = G @ M
    gains = (contrib[:, None] + contrib[None, :] - 2.0 * G * M
             - GM - GM.T)
    np.fill_diagonal(gains, 0.0)
    return gains


def swap_gap(G: np.ndarray, fabric, placement: np.ndarray,
             penal: np.ndarray) -> float:
    """Largest share of a placement's hop-bytes one swap would save."""
    M = sym_weights(fabric, placement, penal)
    total = hop_bytes(G, M)
    if total <= 0.0:
        return 0.0
    return max(0.0, float(swap_gains(G, M).max()) / total)


def block_placement(n: int, allocatable: np.ndarray) -> np.ndarray:
    """Slurm's default: the first ``n`` allocatable node ids."""
    ids = np.flatnonzero(allocatable)
    if len(ids) < n:
        raise ValueError(f"{n} ranks, {len(ids)} allocatable nodes")
    return ids[:n]


def invalid_reason(placement, n: int,
                   allocatable: np.ndarray) -> str | None:
    """Why a placement breaks the deployment's guarantees, or None: it
    must give every rank its own allocatable node."""
    p = np.asarray(placement)
    if p.shape != (n,):
        return f"{p.shape} node ids for {n} ranks"
    if not np.issubdtype(p.dtype, np.integer):
        return f"node ids of type {p.dtype}"
    if (p < 0).any() or (p >= len(allocatable)).any():
        return "node id out of range"
    if len(np.unique(p)) != n:
        return "a node holds two ranks"
    if not allocatable[p].all():
        return "a rank on a node that is not allocatable"
    return None
