"""Traffic of every mix, made from a seed and a mix's parameter file.

Guest communication graphs (dense byte and message matrices) and the
paper's fault draws are built here, so that what the benchmark offers
does not move when the program's own generators change.  The graphs
follow the program's ``repro.workloads.patterns`` as it stood when the
benchmark was written (``chipbench/tests`` checks the copies against
the originals while they stay the same).
"""
from __future__ import annotations

import numpy as np


class Graph:
    """Symmetric byte (``G_v``) and message (``G_m``) matrices."""

    def __init__(self, n: int):
        self.n = n
        self.G_v = np.zeros((n, n))
        self.G_m = np.zeros((n, n))

    def p2p(self, i: int, j: int, nbytes: float, nmsgs: float) -> None:
        if i != j:
            self.G_v[i, j] += nbytes
            self.G_v[j, i] += nbytes
            self.G_m[i, j] += nmsgs
            self.G_m[j, i] += nmsgs

    def ring_all_reduce(self, ranks, nbytes: float, repeats: float) -> None:
        g = len(ranks)
        if g <= 1:
            return
        r = np.asarray(ranks, dtype=np.int64)
        src, dst = r, np.roll(r, -1)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        rows = np.concatenate([src, dst])
        cols = np.concatenate([dst, src])
        np.add.at(self.G_v, (rows, cols), 2.0 * (g - 1) / g * nbytes * repeats)
        np.add.at(self.G_m, (rows, cols), 2 * (g - 1) * repeats)


def _grid3(n: int) -> tuple[int, int, int]:
    best = (1, 1, n)
    for a in range(1, int(round(n ** (1 / 3))) + 2):
        if n % a:
            continue
        m = n // a
        for b in range(a, int(m ** 0.5) + 2):
            if m % b:
                continue
            c = m // b
            if a * b * c == n and c >= b:
                if max(a, b, c) - min(a, b, c) < max(best) - min(best):
                    best = (a, b, c)
    return best


def lammps(n: int, halo_bytes=512e3, collective_bytes=128e3,
           rounds=100) -> Graph:
    """Halo exchange on a periodic 3-D rank grid plus a ring all-reduce
    (LAMMPS rhodopsin's banded pattern, the paper's Fig. 1a)."""
    nx, ny, nz = _grid3(n)
    g = Graph(n)
    for x in range(nx):
        for y in range(ny):
            for z in range(nz):
                i = (x * ny + y) * nz + z
                for dx, dy, dz in ((1, 0, 0), (0, 1, 0), (0, 0, 1)):
                    if (dx and nx < 2) or (dy and ny < 2) or (dz and nz < 2):
                        continue
                    j = (((x + dx) % nx) * ny + (y + dy) % ny) * nz \
                        + (z + dz) % nz
                    g.p2p(i, j, rounds * halo_bytes, rounds)
    g.ring_all_reduce(list(range(n)), collective_bytes, rounds / 10)
    return g


def npb_dt(n: int, seed: int = 7, msg_bytes=640e3, rounds=20) -> Graph:
    """NPB-DT class C-like task DAG: sources -> intermediates -> sinks
    (the paper's Fig. 1b)."""
    rng = np.random.default_rng(seed)
    g = Graph(n)
    perm = rng.permutation(n)
    n_src = max(2, n // 4)
    n_sink = max(2, n // 4)
    src, sink = perm[:n_src], perm[n_src:n_src + n_sink]
    mid = perm[n_src + n_sink:]
    for s in src:
        pool = mid if len(mid) else sink
        for t in rng.choice(pool, size=min(2, len(pool)), replace=False):
            g.p2p(int(s), int(t), rounds * msg_bytes, rounds)
    for m in mid:
        for t in rng.choice(sink, size=min(2, len(sink)), replace=False):
            g.p2p(int(m), int(t), rounds * msg_bytes * 2, rounds)
    return g


GUESTS = {"lammps": lammps, "npb_dt": npb_dt}


def fault_draw(rng: np.random.Generator, n_nodes: int, n_faulty: int,
               p_f: float) -> np.ndarray:
    """The paper's protocol: ``n_faulty`` distinct nodes drawn uniformly,
    each with outage probability ``p_f``."""
    p = np.zeros(n_nodes)
    p[rng.choice(n_nodes, n_faulty, replace=False)] = p_f
    return p
