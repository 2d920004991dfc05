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


# ------------------------------------- the gcd rung against the reference ----

#: a lowered ladder: exact up to 256 pods, the gcd rung up to 128 rows
SMALL_LADDER = CoarseningConfig(threshold=256, max_rows=128)


def _quarter_vcpu_market():
    """One zone's generation-5 m offerings (bench/catalog.py, seed 11: 56
    offerings) under 250m CPU / 256Mi pods: 8 to 384 pods per node, gcd 8."""
    from bench import catalog
    from repro.core import Offering, Request
    from repro.core.provisioner import preprocess

    offs = catalog.offerings(11, ["us-east-1"], ["m"], [5], ["us-east-1a"])
    items = preprocess([Offering(**o) for o in offs], Request(1, 0.25, 0.25))
    return offs, items, compile_market(items)


def _expected_row_counters(market, rows, cfg, tiers):
    """The row solver's counters for ``(req, k, mask)`` rows, worked out
    from each row's residual: the demand less the nodes of the items its
    objective saturates; a row reaches the DP stages when that is positive
    and the rest of the market covers it.  Also the number of rows that
    saturate to residual 0."""
    nodes = market.pods.astype(np.int64) * market.bound.astype(np.int64)
    g = market.pods_gcd
    out = {"dp_rows": 0, "gcd_rows": 0, "dp_cols_needed": 0,
           "dp_cols_computed": 0}
    zero = 0
    for req, k, mask in rows:
        w, q, active = market.solve_inputs(mask)
        neg = (exact.coefficients(np.int64(k), w, q) < 0) & active
        residual = max(req - int(nodes[neg].sum()), 0)
        zero += residual == 0
        if residual == 0 or int(nodes[active & ~neg].sum()) < residual:
            continue
        gcd = residual > cfg.threshold and -(-residual // g) <= cfg.max_rows
        cols = -(-residual // (g if gcd else 1))
        out["dp_rows"] += 1
        out["gcd_rows"] += gcd
        out["dp_cols_needed"] += cols + 1
        out["dp_cols_computed"] += min(t for t in tiers if t > cols)
    return out, zero


@requires_jax
def test_fused_gcd_rung_equals_the_reference_and_counts_its_rows():
    """bracketed_gss_many on ``jax:fused`` over a quarter-vCPU catalog
    (gcd 8) under a lowered ladder, so one batch holds rows at residual 0,
    rows on the exact rung and rows on the gcd rung: pools, α and every
    probe equal the plain reference (bench/reference.py), nothing runs on
    the host, and the programs' own row counters equal the counts worked
    out from each row's residual."""
    from bench import reference
    from repro.core.backend import FusedJaxBackend, _rc_tiers
    from repro.core.provisioner import exclusion_mask

    offs, items, market = _quarter_vcpu_market()
    assert len(offs) == 56 and market.pods_gcd == 8
    reqs = [180, 640, 900, 1010]
    excluded = [set(), {offs[3]["offering_id"], offs[20]["offering_id"]},
                set(), set()]
    masks = [exclusion_mask(items, e) for e in excluded]
    be = make_backend("jax:fused")
    RC = be._shape_key(market, reqs, len(reqs), SMALL_LADDER)[2]
    assert RC == 257            # max(threshold, ceil(1010 / 8)) bucketed
    got = bracketed_gss_many(items, reqs, market=market, excludes=masks,
                             timer=lambda: 0.0, backend=be,
                             coarsening=SMALL_LADDER)
    ref = reference.decide_many(reference.Market(offs, 0.25, 0.25),
                                list(zip(reqs, excluded)))
    for (pool, trace), (r_pool, r_alpha, r_probes) in zip(got, ref):
        assert pool.as_dict() == r_pool and pool.alpha == r_alpha
        assert list(zip(trace.alphas, trace.e_totals)) == r_probes
    info = be.device_cache_info()
    assert info["fused_records"] == 1
    assert info["declined_batches"] == info["host_dp_groups"] == \
        info["fallback_solves"] == 0
    # the prescan solves each decision's grid and the golden program each
    # later probe of its search: the reference's probes, in order
    rows = [(req, int(round(alpha * exact.ALPHA_ONE)), mask)
            for req, mask, (_p, _a, probes) in zip(reqs, masks, ref)
            for alpha, _e in probes]
    want, zero = _expected_row_counters(market, rows, SMALL_LADDER,
                                        _rc_tiers(RC))
    assert {k: info[k] for k in FusedJaxBackend.ROW_COUNTERS} == want
    # the batch mixes every rung
    assert zero > 0 and want["gcd_rows"] > 0
    assert want["dp_rows"] > want["gcd_rows"]
    # the host engine takes the same rung on every gcd row
    stats = solve_ilp_many(items, [r for r, _k, _m in rows],
                           [[exact.k_alpha(k)] for _r, k, _m in rows],
                           market=market, excludes=[m for _r, _k, m in rows],
                           return_stats=True, coarsening=SMALL_LADDER)[1]
    assert sum(s[0] is not None and s[0].coarse == "gcd"
               for s in stats) == want["gcd_rows"]


def test_unset_coarsening_sizes_programs_as_the_served_path():
    """``coarsening=None`` is DEFAULT_COARSENING in ``_shape_key`` and
    ``_coarse_scalars``, as in ``fused_gss_record``: above the threshold a
    gcd market sizes the program by its coarsened width, and at or below
    it every shape is the exact ladder's."""
    from repro.core.backend import FusedJaxBackend as F

    _offs, _items, market = _quarter_vcpu_market()
    for reqs in ([27_600, 20_400], [8_193], [9_000, 100]):
        key = F._shape_key(F, market, reqs, len(reqs))
        assert key == F._shape_key(F, market, reqs, len(reqs),
                                   DEFAULT_COARSENING)
        assert key[2] == 8193
    for reqs in ([8_192], [1_000, 5], [4_000, 8_000, 2]):
        assert F._shape_key(F, market, reqs, len(reqs)) == \
            F._shape_key(F, market, reqs, len(reqs), EXACT)
    assert (F._coarse_scalars(market, None)
            == F._coarse_scalars(market, DEFAULT_COARSENING)).all()
    assert list(F._coarse_scalars(market, None)) == [8192, 4096, 8]
