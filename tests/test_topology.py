import numpy as np
import pytest

from repro.core.topology import (TorusTopology, arrangements,
                                 find_consecutive_healthy, FAULT_PENALTY)


def test_coords_roundtrip():
    t = TorusTopology((4, 3, 5))
    for n in range(t.n_nodes):
        assert t.node_at(t.coords(n)) == n


def test_coords_array_matches_coords():
    t = TorusTopology((3, 4))
    arr = t.coords_array()
    for n in range(t.n_nodes):
        assert tuple(arr[n]) == t.coords(n)


def test_route_length_equals_hop_distance():
    t = TorusTopology((8, 8, 8))
    rng = np.random.default_rng(0)
    hops = t.hop_matrix()
    for _ in range(50):
        u, v = rng.integers(0, t.n_nodes, 2)
        assert len(t.route(int(u), int(v))) == hops[u, v]


def test_route_wraps_shortest_direction():
    t = TorusTopology((8,))
    # 0 -> 7 should go backwards through the wrap link (1 hop)
    r = t.route(0, 7)
    assert len(r) == 1 and r[0].dst == 7


def test_route_endpoints():
    t = TorusTopology((4, 4))
    r = t.route(0, 15)
    assert r[0].src == 0 and r[-1].dst == 15
    # consecutive links chain
    for a, b in zip(r[:-1], r[1:]):
        assert a.dst == b.src


def test_hop_matrix_symmetric_zero_diag():
    t = TorusTopology((4, 4))
    h = t.hop_matrix()
    assert np.allclose(h, h.T)
    assert np.allclose(np.diag(h), 0)
    # max distance on a 4x4 torus is 2+2
    assert h.max() == 4


def test_weight_matrix_no_faults_is_hops():
    t = TorusTopology((4, 4))
    assert np.allclose(t.weight_matrix(None), t.hop_matrix())
    assert np.allclose(t.weight_matrix(np.zeros(16)), t.hop_matrix())


def test_weight_matrix_fault_penalty_eq1():
    t = TorusTopology((8,))
    p = np.zeros(8)
    p[3] = 0.02
    w = t.weight_matrix(p)
    h = t.hop_matrix()
    # 2 -> 4 routes through 3: two links touch node 3
    assert w[2, 4] == h[2, 4] + 2 * FAULT_PENALTY
    # 2 -> 3: one link (2,3) touches node 3
    assert w[2, 3] == h[2, 3] + FAULT_PENALTY
    # 0 -> 1 avoids node 3 entirely
    assert w[0, 1] == h[0, 1]
    # faulty path strictly worse than longest healthy path (paper rationale)
    assert w[2, 3] > h.max()


def test_weight_matrix_straggler_soft_penalty():
    t = TorusTopology((8,))
    s = np.zeros(8)
    s[3] = 0.5
    w = t.weight_matrix(None, straggler=s)
    h = t.hop_matrix()
    assert w[2, 3] == h[2, 3] + 0.5
    assert w[0, 1] == h[0, 1]


def test_neighbors_torus_degree():
    t = TorusTopology((8, 8, 8))
    assert len(t.neighbors(0)) == 6
    t2 = TorusTopology((16, 16))
    assert len(t2.neighbors(17)) == 4


def test_find_consecutive_healthy():
    p = np.zeros(16)
    p[5] = 0.1
    w = find_consecutive_healthy(p, 8)
    assert w is not None and list(w) == list(range(6, 14))
    assert find_consecutive_healthy(p, 11) is None
    assert find_consecutive_healthy(p, 11, wrap=True) is not None
    assert find_consecutive_healthy(np.zeros(4), 8) is None


def test_arrangements_table1():
    arrs = arrangements(256, 3)
    for a in ((4, 8, 8), (4, 4, 16), (2, 8, 16)):
        assert a in arrs
    assert all(np.prod(a) == 256 for a in arrs)


# ------------------------------------ Eq. (1) weights: scalar route oracle
def _scalar_weight_matrix(t, p_f=None, c=1.0, straggler=None):
    """One scalar walk of :meth:`TorusTopology.route_nodes` per pair: the
    loop ``weight_matrix`` ran before its route walk was vectorised."""
    n = t.n_nodes
    if p_f is None:
        p_f = np.zeros(n)
    p_f = np.asarray(p_f, dtype=np.float64)
    base = c * t.hop_matrix()
    faulty = p_f > 0
    slow = None
    if straggler is not None:
        slow = np.asarray(straggler, dtype=np.float64)
        if not np.any(slow > 0):
            slow = None
    if not faulty.any() and slow is None:
        return base
    w = base.copy()
    penal = np.flatnonzero(faulty)
    penal_set = set(int(x) for x in penal)
    slow_idx = set(np.flatnonzero(slow > 0).tolist()) if slow is not None else set()
    interesting = penal_set | slow_idx
    if not interesting:
        return w
    for u in range(n):
        for v in range(n):
            if u == v:
                continue
            nodes = t.route_nodes(u, v)
            extra = 0.0
            for a, b in zip(nodes[:-1], nodes[1:]):
                if a in penal_set or b in penal_set:
                    extra += c * FAULT_PENALTY
                elif a in slow_idx or b in slow_idx:
                    sa = slow[a] if a in slow_idx else 0.0
                    sb = slow[b] if b in slow_idx else 0.0
                    extra += c * max(sa, sb)
            w[u, v] += extra
    return w


def _health(n, seed, n_faulty=0, n_slow=0):
    """Seeded outage and slowdown vectors with distinct slowdown values."""
    rng = np.random.default_rng(seed)
    p_f = np.zeros(n)
    p_f[rng.choice(n, n_faulty, replace=False)] = 0.02
    slow = np.zeros(n)
    slow[rng.choice(n, n_slow, replace=False)] = rng.uniform(0.1, 1.7, n_slow)
    return p_f, slow


def _fault_and_straggler_on_one_link(n):
    # node 5 both faulty and slow; its neighbour 6 slow; 9 and 10 a slow
    # pair of unequal slowdowns (the max of the two is taken)
    p_f, slow = np.zeros(n), np.zeros(n)
    p_f[5] = 0.3
    slow[[5, 6, 9, 10]] = [0.37, 0.37, 0.5, 1.25]
    return p_f, slow


@pytest.mark.parametrize("dims, health, c", [
    ((8,), lambda n: _health(n, 1, n_faulty=2), 1.0),          # ties +1
    ((7,), lambda n: _health(n, 2, n_faulty=1, n_slow=2), 1.0),  # odd
    ((6, 5), lambda n: _health(n, 3, n_faulty=3), 1.0),
    ((4, 4, 3), lambda n: _health(n, 4, n_faulty=4), 1.0),
    ((8, 8, 8), lambda n: _health(n, 5, n_faulty=16), 1.0),
    ((4, 4, 4), lambda n: _health(n, 6, n_slow=6), 1.0),       # stragglers only
    ((6, 6), _fault_and_straggler_on_one_link, 1.0),
    ((4, 4, 4), lambda n: _health(n, 7, n_faulty=4, n_slow=4), 2.0),
    ((1, 5, 2), lambda n: _health(n, 8, n_faulty=2, n_slow=2), 1.0),
    ((4, 4, 3), lambda n: (np.zeros(n), np.zeros(n)), 1.0),    # no faults
], ids=["1d-even", "1d-odd", "2d", "4x4x3", "8x8x8-16-faults",
        "stragglers-only", "fault-and-straggler-one-link", "c2",
        "unit-dims", "no-faults"])
def test_weight_matrix_equals_scalar_route_walk(dims, health, c):
    t = TorusTopology(dims)
    p_f, slow = health(t.n_nodes)
    w = t.weight_matrix(p_f, c=c, straggler=slow)
    assert w.dtype == np.float64
    assert (w == _scalar_weight_matrix(t, p_f, c=c, straggler=slow)).all()
    if not (p_f > 0).any() and not (slow > 0).any():
        assert (w == c * t.hop_matrix()).all()
        assert w is not t.hop_matrix()       # the memo is never handed out
