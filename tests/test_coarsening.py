"""Demand-coarsening hierarchical DP (DESIGN.md §14): the gcd tier is
bit-identical to the exact engine, the approx tier honours its certified
bound, the fallback ladder degrades to exact, and the fused device plane
agrees with NumPy under coarsening (and declines, counted, what it does
not implement).

All tests are seeded deterministic loops (no hypothesis dependency): the
100+-market gcd sweep is the property harness the tier's exactness claim
rests on.
"""

import numpy as np
import pytest

from repro.core import (CoarseningConfig, DEFAULT_COARSENING,
                        NumpyBackend, bracketed_gss_many, compile_market,
                        exact, make_backend, solve_ilp, solve_ilp_many)

from ._optional import HAVE_JAX, requires_jax
from .strategies import big_market, gcd_market, random_market

NUMPY = NumpyBackend()


def _solve(market, demand, alpha, cfg, backend=None):
    return solve_ilp(market.items, demand, alpha, return_stats=True,
                     market=market, backend=backend, coarsening=cfg)


EXACT = CoarseningConfig(enabled=False)


# ------------------------------------------------------------ gcd tier ----

def test_gcd_coarse_equals_exact_bitwise_100_markets():
    """≥100 randomized GCD-sharing markets × demands above threshold ×
    α incl. both edges: the gcd tier must return the *identical count
    vector and objective* as the uncoarsened engine — the DESIGN.md §14
    exactness theorem, checked bit-for-bit."""
    rng = np.random.default_rng(1234)
    cfg = CoarseningConfig(threshold=512, max_rows=1_000_000)
    n_markets = 0
    n_coarse_rows = 0
    for trial in range(34):
        mult = int(rng.choice([2, 4, 8, 16, 64]))
        market = compile_market(gcd_market(rng, n_items=40, pod_mult=mult))
        assert market.pods_gcd % mult == 0
        n_markets += 1
        for demand in (int(rng.integers(600, 3000)),
                       int(rng.integers(3000, 12000)),
                       int(rng.integers(12000, 30000))):
            for alpha in (0.0, float(rng.uniform(0, 1)), 1.0):
                r_e, s_e = _solve(market, demand, alpha, EXACT)
                r_c, s_c = _solve(market, demand, alpha, cfg)
                assert r_e == r_c, (trial, demand, alpha)
                assert s_e.objective == s_c.objective
                if s_c.residual_demand > cfg.threshold and r_c is not None \
                        and s_c.residual_demand > 0:
                    assert s_c.coarse == "gcd"
                    assert s_c.granularity == market.pods_gcd
                    n_coarse_rows += 1
    assert n_markets >= 34 and n_coarse_rows >= 100


def test_gcd_tier_inert_below_threshold():
    rng = np.random.default_rng(5)
    market = compile_market(gcd_market(rng, n_items=30, pod_mult=8))
    r_d, s_d = _solve(market, 900, 0.0, DEFAULT_COARSENING)
    r_e, s_e = _solve(market, 900, 0.0, EXACT)
    assert r_d == r_e and s_d.coarse == "exact" and s_d.granularity == 1


# --------------------------------------------------------- approx tier ----

def test_approx_within_advertised_bound_at_50k():
    """~50k residual on a gcd-1 market: the greedy-prefix + boundary-window
    solve must (1) report mode approx with a finite certificate, (2) have
    a true gap vs the exact optimum no larger than the certificate, and
    (3) keep the certificate within the configured rel_gap."""
    rng = np.random.default_rng(11)
    market = compile_market(big_market(rng, n_items=600))
    assert market.pods_gcd == 1
    cfg = CoarseningConfig(threshold=8192)
    for demand in (30_000, 50_000, 80_000):
        r_e, s_e = _solve(market, demand, 0.0, EXACT)
        r_c, s_c = _solve(market, demand, 0.0, cfg)
        assert s_c.coarse == "approx"
        assert s_c.granularity == cfg.approx_rows
        true_gap = s_c.objective - s_e.objective
        assert -1e-9 <= true_gap <= s_c.gap_bound + 1e-9
        assert s_c.gap_bound <= cfg.rel_gap * abs(s_e.objective) + 1e-9
        # the selection is feasible and bound-respecting
        assert sum(c * it.pods for c, it in zip(r_c, market.items)) >= demand
        assert all(0 <= c <= it.t3 for c, it in zip(r_c, market.items))


def test_approx_fallback_when_certificate_violated():
    """rel_gap=0 makes every certificate fail: the row must be re-solved
    exactly (coarse == approx_fallback) and match the exact engine
    bit-for-bit."""
    rng = np.random.default_rng(11)
    market = compile_market(big_market(rng, n_items=600))
    strict = CoarseningConfig(threshold=8192, rel_gap=0.0)
    r_f, s_f = _solve(market, 50_000, 0.0, strict)
    r_e, s_e = _solve(market, 50_000, 0.0, EXACT)
    assert s_f.coarse == "approx_fallback" and s_f.gap_bound == 0.0
    assert r_f == r_e and s_f.objective == s_e.objective


def test_exact_fallback_below_threshold_and_disabled_ladder():
    """Below threshold → exact; allow_approx=False on a gcd-1 market →
    exact even far above threshold; enabled=False → exact everywhere."""
    rng = np.random.default_rng(11)
    market = compile_market(big_market(rng, n_items=600))
    cfg = CoarseningConfig(threshold=8192)
    r_e, s_e = _solve(market, 5000, 0.0, EXACT)
    r_b, s_b = _solve(market, 5000, 0.0, cfg)
    assert r_b == r_e and s_b.coarse == "exact" and s_b.granularity == 1
    noapx = CoarseningConfig(threshold=8192, allow_approx=False)
    r_n, s_n = _solve(market, 50_000, 0.0, noapx)
    r_x, _ = _solve(market, 50_000, 0.0, EXACT)
    assert s_n.coarse == "exact" and r_n == r_x


def test_alpha_grid_rows_share_coarse_work():
    """solve_ilp_many across mixed scales: per-row tier labels follow the
    ladder, and every row equals its single-row solve (sparse-saturation
    sharing must not change results)."""
    rng = np.random.default_rng(17)
    market = compile_market(big_market(rng, n_items=400))
    cfg = CoarseningConfig(threshold=8192)
    reqs = [5000, 30_000, 30_000, 120_000]
    grids = [[0.0, 0.5], [0.0, 0.5], [0.0], [0.0]]
    many, stats = solve_ilp_many(market.items, reqs, grids, market=market,
                                 return_stats=True, coarsening=cfg)
    for d, (req, grid) in enumerate(zip(reqs, grids)):
        for a, alpha in enumerate(grid):
            r1, s1 = _solve(market, req, alpha, cfg)
            assert many[d][a] == r1
            assert stats[d][a].objective == s1.objective
            assert stats[d][a].coarse == s1.coarse
    # identical (objective, residual) rows dedupe onto one plan: the two
    # 30k α=0.0 rows must agree exactly
    assert many[1][0] == many[2][0]


# ----------------------------------------------- backend equivalence ----

@requires_jax
def test_fused_gss_agrees_with_numpy_under_gcd_coarsening():
    """bracketed_gss_many through the fused device plane ≡ the NumPy
    engine on a gcd-8 market with coarsening active above a lowered
    threshold — pools, α*, and counts all identical."""
    rng = np.random.default_rng(23)
    market = compile_market(gcd_market(rng, n_items=80, pod_mult=8))
    cfg = CoarseningConfig(threshold=1000, max_rows=100_000)
    reqs = [12_000, 16_000, 900, 14_444]
    fake = lambda: 0.0                                     # noqa: E731
    # the device plane must *accept* a gcd-regime batch (decline would
    # silently fall back to the host and prove nothing)
    rec = make_backend("jax:fused").fused_gss_record(
        market.items, market, reqs, [None] * len(reqs),
        exact.alpha_grid(9), 0.01, coarsening=cfg)
    assert rec is not None
    out_n = bracketed_gss_many(market.items, reqs, market=market,
                               timer=fake, backend=NUMPY, coarsening=cfg)
    out_j = bracketed_gss_many(market.items, reqs, market=market,
                               timer=fake,
                               backend=make_backend("jax:fused"),
                               coarsening=cfg)
    out_e = bracketed_gss_many(market.items, reqs, market=market,
                               timer=fake, backend=NUMPY, coarsening=EXACT)
    for (pn, tn), (pj, tj), (pe, te) in zip(out_n, out_j, out_e):
        if pn is None:
            assert pj is None and pe is None
            continue
        assert pn.counts == pj.counts == pe.counts
        assert pn.alpha == pj.alpha == pe.alpha
        assert tn.alphas == tj.alphas


@requires_jax
def test_fused_record_declines_approx_regime():
    """Above threshold on a gcd-1 market the fused device plane must
    decline (approx runs on the host), and the host paths still agree."""
    rng = np.random.default_rng(31)
    market = compile_market(big_market(rng, n_items=120, t3_lo=50,
                                       t3_hi=400))
    assert market.pods_gcd == 1
    cfg = CoarseningConfig(threshold=2000)
    jb = make_backend("jax:fused")
    rec = jb.fused_gss_record(market.items, market, [30_000], [None],
                              exact.alpha_grid(9), 0.01, coarsening=cfg)
    assert rec is None and jb.declined_batches == 1
    fake = lambda: 0.0                                     # noqa: E731
    out_n = bracketed_gss_many(market.items, [30_000], market=market,
                               timer=fake, backend=NUMPY, coarsening=cfg)
    out_j = bracketed_gss_many(market.items, [30_000], market=market,
                               timer=fake, backend=jb, coarsening=cfg)
    (pn, _), (pj, _) = out_n[0], out_j[0]
    if pn is None:
        assert pj is None
    else:
        assert pn.counts == pj.counts and pn.alpha == pj.alpha


# -------------------------------------------------- sim scenario family ----

def test_high_demand_scenario_engages_coarse_tier():
    """The sim-layer stress family must actually land in the coarse
    regime: its generated catalog compiles to a gcd ≥ 8 market and a
    solve at the scenario's demand reports a coarse tier (not exact)."""
    from repro.core.provisioner import preprocess
    from repro.sim import high_demand_scenario

    sc = high_demand_scenario()
    market = compile_market(preprocess(sc.build_catalog(), sc.request()))
    assert market.pods_gcd >= 8
    pool, stats = _solve(market, sc.pods, 0.5, DEFAULT_COARSENING)
    assert pool is not None
    assert stats.coarse in ("gcd", "approx")
    # round-trippable spec (trace-header contract) with the family's knobs
    assert sc == type(sc).from_dict(sc.to_dict())
    small = high_demand_scenario(pods=40_000)
    assert small.pods == 40_000 and small.name == "high_demand_40000"
