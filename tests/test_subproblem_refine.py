"""One device dispatch for all of a multilevel level's refine sub-problems.

``mapping.refine_subproblems`` hands every disjoint sub-problem of an
uncoarsening level to ``mapping_jax.refine_subproblems``, which asks for
each through ``refine_many`` (its own guest, its one-row placement) and
refines them all as the lanes of one vmapped program, dispatched by the
first call.  At float64 each lane accepts the swaps its own
``refine_many`` dispatch would accept, so placements are bit-identical to
one dispatch per sub-problem and to the NumPy backend; where the jax
kernels cannot serve, the per-sub-problem loop runs as before.
"""
import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import backend, mapping, multilevel, spans  # noqa: E402
from repro.core.fattree import FatTreeTopology  # noqa: E402
from repro.core.topology import TorusTopology  # noqa: E402
from repro.workloads.patterns import npb_dt_like  # noqa: E402


def _faults(n_nodes: int, n_faulty: int, seed: int) -> np.ndarray:
    p_f = np.zeros(n_nodes)
    p_f[np.random.default_rng(seed).choice(n_nodes, n_faulty,
                                           replace=False)] = 0.02
    return p_f


def _guest(n: int, density: float, seed: int) -> np.ndarray:
    """Symmetric integer-weight guest: float64 sums on it are exact."""
    rng = np.random.default_rng(seed)
    W = rng.integers(1, 9, (n, n)) * (rng.random((n, n)) < density)
    W = np.triu(W, 1).astype(np.float64)
    return W + W.T


@pytest.fixture
def logged(monkeypatch):
    """(name, args) of every span closed while the test runs."""
    log = []

    class LoggedSpan(spans.span):
        def __exit__(self, *exc):
            super().__exit__(*exc)
            log.append((self.name, self.args))

    monkeypatch.setattr(spans, "span", LoggedSpan)
    return log


def _names(log) -> list[str]:
    return [name for name, _ in log]


def _distances(case: str):
    if case == "dense":
        topo = TorusTopology((4, 4, 4))
        return topo.weight_matrix(_faults(64, 5, 1))
    if case == "fattree":
        topo = FatTreeTopology(8)
        return topo.lazy_distance(_faults(topo.n_nodes, 6, 2))
    topo = TorusTopology((4, 4, 8))
    return topo.lazy_distance(_faults(topo.n_nodes, 6, 3))


def _level(case: str):
    """(G, D, groups, placements): mixed sizes 4-8 (lanes of own width 4
    and 8, rows of different sparse widths) and 6 lanes padded to 8;
    ``dense-subguest`` adds a 16-rank sub-problem dense enough for the
    ``swap_select`` branch."""
    D = _distances("dense" if case == "dense-subguest" else case)
    G = _guest(64, 0.45, seed=4)
    sizes = [4, 8, 5, 7, 6, 4]
    if case == "dense-subguest":
        G[48:, 48:] = 3.0
        np.fill_diagonal(G, 0.0)
        sizes.append(16)
    rng = np.random.default_rng(5)
    procs = rng.permutation(48)
    bounds = np.cumsum([0] + sizes)
    groups = [procs[a:b] for a, b in zip(bounds, bounds[1:])]
    if case == "dense-subguest":
        groups[-1] = np.arange(48, 64)
    nodes = rng.permutation(D.shape[0])
    placements = [nodes[a:b] for a, b in zip(bounds, bounds[1:])]
    return G, D, groups, placements


@pytest.mark.parametrize("case", ["dense", "fattree", "faulty-torus",
                                  "dense-subguest"])
def test_batch_equals_one_dispatch_per_subproblem(case, logged):
    """The level's one dispatch (one ``refine.subproblems`` span, ``S``
    its sub-problems, no ``refine``) equals one ``refine_many`` dispatch
    per sub-problem; with a dense sub-problem the level makes those
    dispatches itself, as before."""
    from repro.core import mapping_jax

    G, D, groups, placements = _level(case)
    with backend.use("jax") as be:
        assert be.dtype == "float64"
        want = [mapping_jax.refine_many(G[np.ix_(F, F)], D, p[None])[0]
                for F, p in zip(groups, placements)]
        del logged[:]
        got = mapping_jax.refine_subproblems(G, D, groups, placements)
    names = _names(logged)
    if case == "dense-subguest":
        assert names.count("refine") == len(groups)
        assert "refine.subproblems" not in names
    else:
        assert "refine" not in names
        assert logged[-1] == ("refine.subproblems", {"S": len(groups),
                                                     "n": 8})
        assert names.count("refine.prepare") == names.count(
            "refine.wait") == 1
    assert len(got) == len(groups)
    moved = 0
    for p, out, ref in zip(placements, got, want):
        np.testing.assert_array_equal(out, ref)
        assert np.array_equal(np.sort(out), np.sort(p))
        moved += not np.array_equal(out, p)
    assert moved                                # the refines did work


def test_a_refine_many_wrapper_sees_every_subproblem(monkeypatch, logged):
    """Each sub-problem reaches ``refine_many`` with its own guest and
    one-row placement, and its result is what that call returns: a
    wrapper that hands the input back unchanged leaves every sub-problem
    unrefined and no batch is dispatched."""
    from repro.core import mapping_jax

    G, D, groups, placements = _level("fattree")
    real = mapping_jax.refine_many
    seen = []

    def watching(G_w, D_, P, *args, **kw):
        seen.append((G_w, np.array(P)))
        return real(G_w, D_, P, *args, **kw)

    def unchanged(G_w, D_, P, *args, **kw):
        return np.array(P, copy=True)

    with backend.use("jax"):
        monkeypatch.setattr(mapping_jax, "refine_many", watching)
        got = mapping_jax.refine_subproblems(G, D, groups, placements)
        assert len(seen) == len(groups)
        for (G_w, P), F, p in zip(seen, groups, placements):
            np.testing.assert_array_equal(G_w, G[np.ix_(F, F)])
            np.testing.assert_array_equal(P, p[None])
        assert any(not np.array_equal(a, b)
                   for a, b in zip(got, placements))
        monkeypatch.setattr(mapping_jax, "refine_many", unchanged)
        del logged[:]
        kept = mapping_jax.refine_subproblems(G, D, groups, placements)
    assert not logged
    for a, b in zip(kept, placements):
        np.testing.assert_array_equal(a, b)


def test_lane_refuses_another_placement():
    """A lane answers only for the placement it was batched with."""
    from repro.core import mapping_jax

    G, D, groups, placements = _level("dense")
    batch = mapping_jax.SubproblemBatch(G, D, groups, placements)
    F, p = groups[0], placements[0]
    with backend.use("jax"), pytest.raises(ValueError, match="lane 0"):
        mapping_jax.refine_many(G[np.ix_(F, F)], D, p[::-1][None],
                                lane=(batch, 0))


@pytest.mark.parametrize("n", [0, 1, 5, 40])
def test_sparse_rows_list_each_rows_nonzeros_in_order(n):
    """``_sparse_rows`` (one lane of ``_csr_rows``) lists each row's
    nonzero columns in ascending order with their weights, the diagonal
    left out, padded with (index 0, weight 0.0) to a multiple of 4 no
    wider than the power of two of ``n``."""
    from repro.core import mapping_jax

    G = _guest(n, 0.3, seed=n)
    if n:
        G[np.diag_indices(n)] = 7.0
        G[0] = G[:, 0] = 0.0                   # an empty row
    idx, val, k, G0 = mapping_jax._sparse_rows(G)
    want = np.array(G, dtype=np.float64)
    np.fill_diagonal(want, 0.0)
    np.testing.assert_array_equal(G0, want)
    widest = max([1] + [np.count_nonzero(row) for row in want])
    assert k == min(mapping_jax._pow2(n), (widest + 3) & ~3)
    assert idx.shape == val.shape == (n, k)
    for r in range(n):
        cols = np.flatnonzero(want[r])
        np.testing.assert_array_equal(idx[r, :len(cols)], cols)
        np.testing.assert_array_equal(val[r, :len(cols)], want[r, cols])
        assert not idx[r, len(cols):].any() and not val[r, len(cols):].any()


def _depth_first_map(G_w, nodes, coords, D, rng, coarse_target=160):
    """``multilevel_map`` as it was with one refine per sub-problem,
    made at the end of its ``descend`` call (the depth-first order)."""
    n = G_w.shape[0]
    nodes = mapping.snake_order(nodes, coords)[:n]
    levels, Gc, sizes_c = multilevel.coarsen(G_w, coarse_target)
    kids = [multilevel._children_lists(lvl.match, int(lvl.match.max()) + 1)
            for lvl in levels]
    procs = [multilevel._children_lists(lab, int(lab.max()) + 1)
             for lab in multilevel.uncoarsen_map(levels)]
    placement = np.full(n, -1, dtype=np.int64)

    def descend(li, members, chunk):
        lvl = levels[li]
        sub_chunks = ([np.asarray(chunk)] if len(members) == 1 else
                      multilevel._weighted_drb(
                          lvl.G[np.ix_(members, members)],
                          lvl.sizes[members], chunk, coords, D, rng))
        if li == 0:
            for local, m in enumerate(members):
                placement[m] = sub_chunks[local][0]
            return
        for local, m in enumerate(members):
            descend(li - 1, kids[li - 1][int(m)], sub_chunks[local])
        F = np.concatenate([procs[li - 1][int(m)] for m in members])
        if 4 <= len(F) <= multilevel._LOCAL_REFINE_MAX:
            placement[F] = mapping._pairwise_refine(G_w[np.ix_(F, F)], D,
                                                    placement[F])

    top = multilevel._weighted_drb(Gc, sizes_c, nodes, coords, D, rng)
    for v in range(Gc.shape[0]):
        descend(len(levels) - 1, kids[-1][v], top[v])
    candidates = mapping.refine_batch(
        G_w, D, np.stack([placement, mapping.snake_order(nodes, coords)[:n]]))
    return candidates[int(np.argmin(
        mapping.hop_bytes_batch(G_w, D, candidates)))]


def test_level_order_refine_equals_depth_first():
    """Refining each level's sub-problems after the whole descent, finest
    level first, gives the placement of one refine per sub-problem at
    the end of its own descent (NumPy backend)."""
    G, nodes, coords, D = _fattree_case()
    want = _depth_first_map(G, nodes, coords, D, np.random.default_rng(0))
    got = multilevel.multilevel_map(G, nodes, coords, D=D,
                                    rng=np.random.default_rng(0))
    np.testing.assert_array_equal(got, want)


def _fattree_case():
    topo = FatTreeTopology(12)                  # 432 hosts
    D = topo.lazy_distance(_faults(topo.n_nodes, 12, 7))
    G = npb_dt_like(384).comm.G_v
    nodes = np.arange(400)
    return G, nodes, topo.coords_array(), D


def test_multilevel_one_dispatch_per_level(monkeypatch, logged):
    """384-rank NPB-DT coarsens three times (two levels with refine
    sub-problems).  On the jax backend the placement is the NumPy
    backend's, bit for bit; ``refine_many`` is asked once per
    sub-problem and once for the whole-job polish, but dispatches only
    the polish itself (one ``refine`` span): each level with
    sub-problems is one ``refine.subproblems`` span whose ``S`` counts
    them."""
    from repro.core import mapping_jax

    G, nodes, coords, D = _fattree_case()
    levels, _, _ = multilevel.coarsen(G, 160)
    assert len(levels) >= 3

    sub_sizes = []
    vectorized = mapping._pairwise_refine

    def counting(G_sub, D_, p):
        if len(p) < len(G):
            sub_sizes.append(len(p))
        return vectorized(G_sub, D_, p)

    monkeypatch.setattr(mapping, "_pairwise_refine", counting)
    ref = multilevel.multilevel_map(G, nodes, coords, D=D,
                                    rng=np.random.default_rng(0))
    monkeypatch.setattr(mapping, "_pairwise_refine", vectorized)

    calls = []
    real = mapping_jax.refine_many

    def refine_many(*args, **kw):
        calls.append(kw.get("lane") is not None)
        return real(*args, **kw)

    monkeypatch.setattr(mapping_jax, "refine_many", refine_many)
    del logged[:]
    with backend.use("jax") as be:
        fallbacks = be.stats["numpy_fallbacks"]
        out = multilevel.multilevel_map(G, nodes, coords, D=D,
                                        rng=np.random.default_rng(0))
        assert be.stats["numpy_fallbacks"] == fallbacks
    np.testing.assert_array_equal(out, ref)
    assert calls.count(False) == 1
    assert calls.count(True) == len(sub_sizes)
    levels_spans = [a for name, a in logged if name == "refine.subproblems"]
    assert len(levels_spans) == len(levels) - 1
    assert sum(a["S"] for a in levels_spans) == len(sub_sizes)
    assert all(4 <= s <= 8 for s in sub_sizes)
    assert {a["n"] for a in levels_spans} <= {4, 8}
    names = _names(logged)
    assert names.count("refine") == 1
    assert names.count("refine.prepare") == names.count("refine.wait") \
        == len(levels_spans) + 1


@pytest.mark.parametrize("case", ["reference-impl", "straggling-torus"])
def test_per_subproblem_loop_where_jax_cannot_serve(case, logged):
    """Under ``use_reference_impl`` the scalar reference refines every
    sub-problem; on a straggling torus the lazy metric has no implicit
    spec, so NumPy refines them.  Either way no sub-problem batch runs
    and the placement is the NumPy backend's."""
    topo = TorusTopology((4, 4, 8))
    G = npb_dt_like(96, seed=3).comm.weights("volume")
    p_f = _faults(topo.n_nodes, 6, 8)
    if case == "straggling-torus":
        straggler = np.zeros(topo.n_nodes)
        straggler[[5, 40, 77]] = 0.5
        D = topo.lazy_distance(p_f, straggler=straggler)
    else:
        D = topo.weight_matrix(p_f)
    assert len(multilevel.coarsen(G, 24)[0]) >= 2

    def place():
        return multilevel.multilevel_map(
            G, np.arange(110), topo.coords_array(), D=D,
            rng=np.random.default_rng(0), coarse_target=24)

    with (mapping.use_reference_impl() if case == "reference-impl"
          else contextlib.nullcontext()):
        ref = place()
        with backend.use("jax") as be:
            fallbacks = be.stats["numpy_fallbacks"]
            del logged[:]
            out = place()
            assert "refine.subproblems" not in _names(logged)
            if case == "straggling-torus":
                assert be.stats["numpy_fallbacks"] > fallbacks
    np.testing.assert_array_equal(out, ref)

