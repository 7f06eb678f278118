"""Monte-Carlo replica engine: statistics, determinism, executors.

Covers the three contracts ``benchmarks/clustersim.py --check`` rests on:

* the bootstrap statistics are correct (closed-form checks, degenerate
  inputs, paired comparisons);
* every replica is bit-identical to a standalone ``run_preset`` call
  with the same seed — across all presets, the process-pool executor,
  and the vectorized paper-mode path;
* a fixed-seed :class:`SummaryStats` regression pins the aggregate
  numbers so silent changes to preset RNG streams fail loudly.
"""
import numpy as np
import pytest

from repro.sim.replicas import (
    PairedComparison, ReplicaSet, SummaryStats, bootstrap_ci,
    paired_compare, paper_replica_vector, run_replicas, summarize,
    _flat_policy_rows,
)
from repro.sim.scenarios import SCENARIOS, run_preset

from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()


# ------------------------------------------------------------ statistics
def test_bootstrap_ci_matches_normal_theory():
    rng = np.random.default_rng(0)
    x = rng.normal(loc=3.0, scale=2.0, size=400)
    lo, hi = bootstrap_ci(x, B=4000, alpha=0.05, seed=1)
    se = x.std(ddof=1) / np.sqrt(x.size)
    assert lo < x.mean() < hi
    # percentile bootstrap ~ mean +/- 1.96 se for a big normal sample
    assert lo == pytest.approx(x.mean() - 1.96 * se, abs=0.6 * se)
    assert hi == pytest.approx(x.mean() + 1.96 * se, abs=0.6 * se)


def test_bootstrap_ci_level_monotone():
    rng = np.random.default_rng(3)
    x = rng.exponential(size=200)
    lo95, hi95 = bootstrap_ci(x, B=2000, alpha=0.05, seed=2)
    lo50, hi50 = bootstrap_ci(x, B=2000, alpha=0.50, seed=2)
    assert lo95 <= lo50 <= hi50 <= hi95


def test_bootstrap_ci_degenerate_inputs():
    assert bootstrap_ci([4.2]) == (4.2, 4.2)            # single observation
    assert bootstrap_ci([1.5] * 10) == (1.5, 1.5)       # zero variance
    lo, hi = bootstrap_ci([1.0, 2.0], B=200, seed=0)    # tiny n still sane
    assert 1.0 <= lo <= hi <= 2.0
    with pytest.raises(ValueError):
        bootstrap_ci([])
    with pytest.raises(ValueError):
        bootstrap_ci([1.0, 2.0], alpha=1.5)
    with pytest.raises(ValueError):
        bootstrap_ci([1.0, 2.0], B=0)
    with pytest.raises(ValueError):
        bootstrap_ci(np.zeros((2, 2)))


def test_bootstrap_ci_seed_reproducible():
    x = np.random.default_rng(5).normal(size=50)
    assert bootstrap_ci(x, seed=9) == bootstrap_ci(x, seed=9)
    assert bootstrap_ci(x, seed=9) != bootstrap_ci(x, seed=10)


def test_summarize_fields_consistent():
    x = np.random.default_rng(1).normal(size=100)
    s = summarize(x, metric="m", B=500, alpha=0.05, seed=0)
    assert isinstance(s, SummaryStats)
    assert s.metric == "m" and s.n == 100
    assert s.ci_low <= s.mean <= s.ci_high
    assert s.p05 <= s.p50 <= s.p95
    assert s.std == pytest.approx(x.std(ddof=1))
    assert summarize([7.0]).std == 0.0


def test_paired_compare_detects_shift():
    rng = np.random.default_rng(2)
    b = rng.normal(loc=5.0, scale=1.0, size=64)
    a = b - rng.uniform(0.5, 1.5, size=64)     # a strictly smaller
    cmp = paired_compare(a, b, a="tofa", b="linear", B=1000, seed=0)
    assert isinstance(cmp, PairedComparison)
    assert cmp.significant and cmp.delta_ci_low > 0
    assert cmp.win_rate == 1.0
    assert cmp.p_value <= 2 / 1001
    assert cmp.delta == pytest.approx(float((b - a).mean()))


def test_paired_compare_null_not_significant():
    x = np.random.default_rng(4).normal(size=64)
    cmp = paired_compare(x, x, B=500)
    assert cmp.delta == 0.0 and not cmp.significant
    assert cmp.win_rate == 0.0 and cmp.p_value > 0.5
    with pytest.raises(ValueError):
        paired_compare([1.0, 2.0], [1.0])


# ----------------------------------------------------------- determinism
def _strip_wall(rows):
    return {pol: {k: v for k, v in r.items() if k != "place_time_s"}
            for pol, r in rows.items()}


@pytest.mark.parametrize("preset", sorted(SCENARIOS))
def test_replica_bit_identical_to_standalone(preset):
    """run_replicas(seeds=[k]) reproduces run_preset(seed=k) bit-for-bit
    (wall-clock fields excepted) for every registered preset."""
    seed = 11
    rs = run_replicas(preset, seeds=[seed], fast=True)
    ref = _strip_wall(_flat_policy_rows(run_preset(preset, seed=seed,
                                                   fast=True)))
    for pol, row in ref.items():
        for k, v in row.items():
            assert rs.metrics[pol][k][0] == v, (preset, pol, k)


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=8, deadline=None)
def test_replica_bit_identical_property(seed):
    rs = run_replicas("fat-tree", seeds=[seed], fast=True)
    ref = _strip_wall(_flat_policy_rows(run_preset("fat-tree", seed=seed,
                                                   fast=True)))
    for pol, row in ref.items():
        for k, v in row.items():
            assert rs.metrics[pol][k][0] == v, (seed, pol, k)


def test_process_pool_equals_serial():
    a = run_replicas("fat-tree", n_replicas=4, fast=True, executor="serial")
    b = run_replicas("fat-tree", n_replicas=4, fast=True,
                     executor="process", max_workers=2)
    assert a.seeds == b.seeds and a.policies == b.policies
    for pol in a.metrics:
        for k in a.metrics[pol]:
            if k == "place_time_s":
                continue
            assert np.array_equal(a.metrics[pol][k], b.metrics[pol][k])


def test_no_worker_processes_while_holding_an_accelerator(monkeypatch):
    """A chip serves one process: with the jax backend on an accelerator,
    ``auto`` runs serial even where it would pool, and ``process``
    refuses."""
    import concurrent.futures

    from repro.sim import replicas

    monkeypatch.setattr(replicas, "on_accelerator", lambda: True)

    def no_pool(*a, **k):
        raise AssertionError("a worker pool was started")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", no_pool)
    rs = run_replicas("fat-tree", n_replicas=8, fast=True, executor="auto",
                      max_workers=4)
    assert rs.n_replicas == 8
    with pytest.raises(RuntimeError, match="accelerator"):
        run_replicas("fat-tree", n_replicas=2, fast=True,
                     executor="process", max_workers=2)


def test_vectorized_paper_path_equals_event_path():
    vec = run_replicas("paper-fig4-5", n_replicas=3, fast=True)
    evt = run_replicas("paper-fig4-5", n_replicas=3, fast=True,
                       vectorize="never")
    for pol in vec.metrics:
        for k in vec.metrics[pol]:
            if k == "place_time_s":
                continue
            assert np.array_equal(vec.metrics[pol][k],
                                  evt.metrics[pol][k]), (pol, k)


def test_vectorized_single_replica_matches_standalone():
    ref = _strip_wall(_flat_policy_rows(run_preset("paper-fig4-5", seed=4,
                                                   fast=True)))
    vec = _strip_wall(_flat_policy_rows(paper_replica_vector(seed=4,
                                                             fast=True)))
    assert vec == ref


# ------------------------------------------------------------- aggregate
def test_replicaset_compare_and_summary():
    rs = run_replicas("dragonfly", n_replicas=6, fast=True)
    assert isinstance(rs, ReplicaSet) and rs.n_replicas == 6
    s = rs.summary("tofa")
    assert s.n == 6 and s.ci_low <= s.mean <= s.ci_high
    cmp = rs.compare(B=500)
    assert cmp.a == "tofa" and cmp.b == "linear" and cmp.n == 6
    assert 0.0 <= cmp.win_rate <= 1.0
    assert cmp.delta_ci_low <= cmp.delta <= cmp.delta_ci_high
    with pytest.raises(KeyError):
        rs.samples("no-such-policy")


def test_run_replicas_argument_validation():
    with pytest.raises(KeyError):
        run_replicas("no-such-preset", n_replicas=1)
    with pytest.raises(ValueError):
        run_replicas("fat-tree")                       # neither
    with pytest.raises(ValueError):
        run_replicas("fat-tree", n_replicas=2, seeds=[0, 1])   # both
    with pytest.raises(ValueError):
        run_replicas("fat-tree", n_replicas=0)
    with pytest.raises(ValueError):
        run_replicas("fat-tree", n_replicas=1, executor="threads")
    with pytest.raises(ValueError):
        run_replicas("fat-tree", n_replicas=1, vectorize="always")


def test_summary_stats_regression_fat_tree_32():
    """Fixed-seed pin: fast fat-tree across 32 replicas, B=1000.

    These numbers change only if a preset RNG stream, the placement
    policies, or the simulator semantics change — all of which must be
    deliberate, visible events.
    """
    rs = run_replicas("fat-tree", n_replicas=32, fast=True)
    s_tofa = rs.summary("tofa", B=1000, seed=0)
    s_lin = rs.summary("linear", B=1000, seed=0)
    cmp = rs.compare(B=1000, seed=0)
    assert s_tofa.mean == pytest.approx(PINNED["tofa_mean"], rel=1e-9)
    assert s_tofa.ci_low == pytest.approx(PINNED["tofa_ci_low"], rel=1e-9)
    assert s_tofa.ci_high == pytest.approx(PINNED["tofa_ci_high"], rel=1e-9)
    assert s_lin.mean == pytest.approx(PINNED["linear_mean"], rel=1e-9)
    assert cmp.win_rate == pytest.approx(PINNED["win_rate"], rel=1e-9)
    assert cmp.delta == pytest.approx(PINNED["delta"], rel=1e-9)


PINNED = {
    # regenerate: run_replicas("fat-tree", n_replicas=32, fast=True),
    # summary(B=1000, seed=0) / compare(B=1000, seed=0)
    "tofa_mean": 1.0624243500000001,
    "tofa_ci_low": 0.93415248328125,
    "tofa_ci_high": 1.2095726003124998,
    "linear_mean": 1.0694688874999998,
    "win_rate": 0.53125,
    "delta": 0.007044537499999982,
}
