"""§3.4 importance coefficients: numerics + the unbiasedness property (eq. 5).

The decisive test: over repeated cache draws + GNS neighbor sampling, the
weighted aggregation Σ w·h must converge to the full-neighborhood mean.
This is exactly eq. (5)/(B.15) — the property Theorem 1's proof rests on.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # bare env: seeded fallback shim
    from _hypothesis_fallback import given, settings, st

from repro.featurestore import CacheConfig
from repro.core.importance import (cache_hit_prob, importance_coefficients,
                                   solve_inclusion_lambda)
from repro.core.sampler import GNSSampler, SamplerConfig
from repro.core.variance import full_neighbor_mean, sampled_mean_once
from repro.graph.generate import powerlaw_graph


# ---------------------------------------------------------------------------
# unit / numeric behavior
# ---------------------------------------------------------------------------

def test_cache_hit_prob_limits():
    p = np.array([0.0, 1e-9, 0.5, 1.0 - 1e-13])
    pc = cache_hit_prob(p, cache_size=100)
    assert pc[0] == 0.0
    assert pc[1] == pytest.approx(1e-7, rel=1e-3)   # ~ |C|*p for tiny p
    assert pc[2] > 1 - 1e-12                         # saturates
    assert np.all((0 <= pc) & (pc <= 1))


def test_solve_lambda_calibrates_to_cache_size():
    """Non-degenerate case: Σ_i (1 - exp(-λ p_i)) == |C| at the solution."""
    rng = np.random.default_rng(0)
    p = rng.pareto(1.5, size=5000) + 1e-6
    p /= p.sum()
    for c in (10, 100, 1000):
        lam = solve_inclusion_lambda(p, c)
        assert lam is not None and lam >= c
        total = cache_hit_prob(p, c, lam=lam).sum()
        assert total == pytest.approx(c, rel=1e-4)


def test_solve_lambda_degenerate_cache_covers_support():
    """|C| >= positive support: every node is included w.p. 1 (λ* = ∞) —
    must warn and fall back to the independence approximation, not fail to
    bracket."""
    p = np.full(50, 1.0 / 50)
    for c in (50, 51, 500):
        with pytest.warns(RuntimeWarning, match="positive-probability nodes"):
            assert solve_inclusion_lambda(p, c) is None


def test_solve_lambda_all_zero_probs():
    with pytest.warns(RuntimeWarning, match="all-zero"):
        assert solve_inclusion_lambda(np.zeros(100), 10) is None


def test_cache_hit_prob_degenerate_lam_falls_back():
    """A degenerate λ (inf / nan / <= 0) must warn and return the
    independence-approximation probabilities, which stay in [0, 1]."""
    p = np.array([0.0, 1e-4, 0.5])
    expect = cache_hit_prob(p, 20)                # independence path
    for bad in (np.inf, np.nan, 0.0, -3.0):
        with pytest.warns(RuntimeWarning, match="degenerate lam"):
            got = cache_hit_prob(p, 20, lam=bad)
        np.testing.assert_array_equal(got, expect)
        assert np.all((0 <= got) & (got <= 1))


def test_store_lambda_degenerate_cache_still_refreshes():
    """End-to-end: a FeatureStore whose cache covers the whole graph must
    refresh cleanly (λ falls back to None -> eq. 11 weights)."""
    import warnings as _w
    from repro.featurestore import FeatureStore
    g = powerlaw_graph(300, avg_degree=6, seed=0)
    feats = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 8)).astype(np.float32)
    store = FeatureStore(feats, g, CacheConfig(fraction=1.0))
    with _w.catch_warnings():
        _w.simplefilter("ignore", RuntimeWarning)
        gen = store.refresh(np.random.default_rng(0))
    assert gen.lam is None
    assert gen.state.in_cache.all()


@given(p=st.floats(1e-12, 0.99), c=st.integers(1, 10_000))
@settings(max_examples=200, deadline=None)
def test_cache_hit_prob_monotone_bounded(p, c):
    pc = float(cache_hit_prob(np.array([p]), c)[0])
    assert 0.0 <= pc <= 1.0
    assert pc >= p * 0.9999 or c == 1  # more draws -> higher prob
    pc2 = float(cache_hit_prob(np.array([p]), c + 1)[0])
    assert pc2 >= pc - 1e-15


@given(
    probs=st.lists(st.floats(1e-8, 0.2), min_size=1, max_size=8),
    cache_size=st.integers(1, 1000),
    fanout=st.integers(1, 32),
    ncv=st.integers(0, 64),
)
@settings(max_examples=200, deadline=None)
def test_coefficients_positive_bounded(probs, cache_size, fanout, ncv):
    p = np.array(probs)
    for mode in ("ht", "paper"):
        c = importance_coefficients(p, cache_size, fanout, np.full_like(p, ncv),
                                    mode=mode)
        assert np.all(c > 0)
        if mode == "ht":
            assert np.all(c <= 1.0 + 1e-9)   # an inclusion probability


# ---------------------------------------------------------------------------
# the eq. (5) unbiasedness property (Monte-Carlo)
# ---------------------------------------------------------------------------

def _mc_estimates(g, h, nodes, mode, trials, fanout=6, fraction=0.05):
    cfg = SamplerConfig(fanouts=(fanout,), batch_size=len(nodes),
                        cache=CacheConfig(fraction=fraction, period=1),
                        importance_mode=mode)
    s = GNSSampler(g, cfg, h.astype(np.float32), np.zeros(g.num_nodes, np.int32))
    ests = np.zeros((trials, len(nodes), h.shape[1]))
    for t in range(trials):
        s.refresh_cache(np.random.default_rng(1000 + t), version=t)
        ests[t] = sampled_mean_once(s, nodes, h, np.random.default_rng(2000 + t))
    return ests


@pytest.mark.slow
def test_gns_weight_sum_unbiased():
    """Exact form of eq. (5): with h ≡ 1, E[Σ_k w] must be exactly 1.

    This isolates the importance-weight bookkeeping from feature noise:
    any systematic error in eq. (11)/(12) or the top-up weights shows up as a
    deterministic shift of the weight-sum mean.
    """
    g = powerlaw_graph(3000, avg_degree=12, seed=5)
    h = np.ones((g.num_nodes, 1))
    # probe a degree-diverse set including hubs (cache interacts with hubs)
    order = np.argsort(g.degrees)
    nodes = np.concatenate([order[-16:], order[len(order) // 2: len(order) // 2 + 16]]).astype(np.int64)
    trials = 400
    ests = _mc_estimates(g, h, nodes, "ht", trials)
    mean = ests.mean(axis=0)[:, 0]             # E[Σw] per node
    se = ests.std(axis=0)[:, 0] / np.sqrt(trials)
    z = np.abs(mean - 1.0) / np.maximum(se, 1e-4)
    # systematic bias (signed mean across nodes) must vanish; per-node
    # deviations are MC noise and are checked against their standard errors
    assert abs(np.mean(mean - 1.0)) < 0.02, mean
    assert (z < 5).mean() > 0.9, (mean, z)


@pytest.mark.slow
def test_gns_aggregation_unbiased_zscore():
    """MC mean of the weighted aggregation matches the exact mean within SE."""
    g = powerlaw_graph(3000, avg_degree=12, seed=5)
    rng = np.random.default_rng(0)
    h = rng.normal(size=(g.num_nodes, 4))
    nodes = np.argsort(g.degrees)[-24:].astype(np.int64)
    target = full_neighbor_mean(g, h, nodes)
    trials = 400
    ests = _mc_estimates(g, h, nodes, "ht", trials)
    mean = ests.mean(axis=0)
    se = ests.std(axis=0) / np.sqrt(trials)
    z = np.abs(mean - target) / np.maximum(se, 1e-5)
    assert (z < 5).mean() > 0.95, f"fraction within 5 SE: {(z < 5).mean():.3f}"


def test_gns_topup_unbiased_when_truncation_binds():
    """Upper-layer top-up: rows with 0 < N_C(v) < k keep only the first
    ``need = k - N_C(v)`` non-cached lanes of a k-lane draw by lane rank,
    and that cut binds whenever the draw misses a cached neighbour.  Given
    the cache, E[Σ w·h] must still be the full-neighbourhood mean; a draw
    whose lane order favours part of the neighbour list biases it.  ``h``
    rises with the node id, as does each sorted neighbour list."""
    from math import comb
    k, trials = 10, 4000
    g = powerlaw_graph(3000, avg_degree=12, seed=5)
    h = (np.arange(g.num_nodes, dtype=np.float64) / g.num_nodes)[:, None]
    cfg = SamplerConfig(fanouts=(k, k), batch_size=64,
                        cache=CacheConfig(fraction=0.05, period=1))
    s = GNSSampler(g, cfg, h.astype(np.float32),
                   np.zeros(g.num_nodes, np.int32))
    s.refresh_cache(np.random.default_rng(11), version=0)
    deg = g.degrees
    n_c = np.diff(s.cache_adj.indptr)
    cand = np.where((n_c >= 1) & (n_c < k) & (deg >= k + 2))[0]
    cand = cand[np.argsort(deg[cand], kind="stable")]
    nodes = cand[::max(1, len(cand) // 32)][:32].astype(np.int64)
    assert len(nodes) >= 16
    # the cut binds unless all N_C(v) cached neighbours are among the k drawn
    for v in nodes:
        d, c = int(deg[v]), int(n_c[v])
        assert comb(d - c, k - c) / comb(d, k) < 0.5, (d, c)
    nbrs, mask, w = s._sample_layer(np.repeat(nodes, trials), k,
                                    np.random.default_rng(3),
                                    allow_topup=True)
    est = (np.where(mask, w, 0.0) * h[nbrs, 0]).sum(axis=1)
    est = est.reshape(len(nodes), trials)
    target = full_neighbor_mean(g, h, nodes)[:, 0]
    se = est.std(axis=1) / np.sqrt(trials)
    z = (est.mean(axis=1) - target) / np.maximum(se, 1e-9)
    assert np.abs(z).max() < 5, z


@pytest.mark.slow
def test_gns_variance_decreases_with_cache_size():
    """Theorem 1 trend: larger cache fraction C̃ -> smaller estimator MSE."""
    from repro.core.variance import estimator_mse
    g = powerlaw_graph(3000, avg_degree=12, seed=6)
    rng = np.random.default_rng(0)
    h = rng.normal(size=(g.num_nodes, 8))
    nodes = rng.choice(g.num_nodes, size=64, replace=False).astype(np.int64)
    mse_small = estimator_mse(g, h, nodes, "gns", fanout=5,
                              cache_fraction=0.002, trials=60, seed=1)
    mse_big = estimator_mse(g, h, nodes, "gns", fanout=5,
                            cache_fraction=0.10, trials=60, seed=1)
    assert mse_big < mse_small
