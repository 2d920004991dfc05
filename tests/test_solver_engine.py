"""Batched multi-α ILP engine: cross-validation against the seed solver,
brute force, and the legacy GSS path (DESIGN.md §8).

The engine must be *exact*: every randomized market — including infeasible
demands and the α ∈ {0, 1} edges — has to produce the same objective value
and a feasible, bound-respecting count vector as the seed history-matrix
solver, and the rewired guarded GSS must return pools with identical
E_Total to the legacy per-α path.
"""

import itertools

import numpy as np
import pytest

from repro.core import (KubePACSProvisioner, Request,
                        compile_market, e_total, e_total_batch,
                        generate_catalog, objective_coefficients,
                        pool_metric_arrays, preprocess, solve_ilp,
                        solve_ilp_batch)
from repro.core.gss import bracketed_gss, golden_section_search
from repro.core.ilp import _lp_prune

from tests.oracles import (guarded_gss_reference, solve_ilp_pulp,
                           solve_ilp_reference)
from tests.strategies import mk_item as _mk_item
from tests.strategies import random_market as _random_market


def _objective(items, counts, alpha):
    return float(np.dot(objective_coefficients(items, alpha), counts))


def _check_solution(items, counts, req, alpha, ref_obj):
    assert counts is not None
    assert all(0 <= c <= it.t3 for c, it in zip(counts, items))
    assert sum(c * it.pods for c, it in zip(counts, items)) >= req
    assert _objective(items, counts, alpha) == pytest.approx(ref_obj, abs=1e-8)


# ------------------------------------------------- randomized equivalence ----

def test_batch_equals_single_equals_reference_100_markets():
    """≥100 randomized markets × α grid incl. the {0, 1} edges: the batched
    engine, the per-α engine, and the seed solver agree on feasibility and
    objective, and every returned count vector is feasible and in-bounds."""
    rng = np.random.default_rng(7)
    n_markets = 110
    n_infeasible = 0
    for _ in range(n_markets):
        items = _random_market(rng)
        req = int(rng.integers(0, 90))
        alphas = [0.0, 1.0] + [float(a) for a in rng.uniform(0, 1, size=3)]
        market = compile_market(items)
        batch = solve_ilp_batch(items, req, alphas, market=market)
        for alpha, counts_b in zip(alphas, batch):
            counts_s = solve_ilp(items, req, alpha, market=market)
            counts_r = solve_ilp_reference(items, req, alpha)
            if counts_r is None:
                n_infeasible += 1
                assert counts_b is None and counts_s is None
                continue
            ref_obj = _objective(items, counts_r, alpha)
            _check_solution(items, counts_b, req, alpha, ref_obj)
            _check_solution(items, counts_s, req, alpha, ref_obj)
    assert n_infeasible > 0   # the sweep must exercise the infeasible branch


def test_batch_stats_dp_objectives_match_decoded_counts():
    """return_stats objectives come from the vectorized (A × R+1) value DP;
    they must equal the objective of the independently decoded counts."""
    rng = np.random.default_rng(21)
    for _ in range(15):
        items = _random_market(rng)
        req = int(rng.integers(1, 80))
        alphas = [0.0, 0.04, 0.5, 1.0]
        counts_list, stats = solve_ilp_batch(items, req, alphas,
                                             return_stats=True)
        for alpha, counts, st_ in zip(alphas, counts_list, stats):
            if counts is None:
                assert not np.isfinite(st_.objective)
                continue
            assert st_.objective == pytest.approx(
                _objective(items, counts, alpha), abs=1e-8)


def test_engine_matches_brute_force_small():
    rng = np.random.default_rng(3)
    for _ in range(40):
        items = _random_market(rng, max_items=4, max_t3=6)
        req = int(rng.integers(0, 14))
        alpha = float(rng.uniform(0, 1))
        coef = objective_coefficients(items, alpha)
        best = None
        for xs in itertools.product(*[range(it.t3 + 1) for it in items]):
            if sum(x * it.pods for x, it in zip(xs, items)) < req:
                continue
            c = float(np.dot(coef, xs))
            if best is None or c < best - 1e-12:
                best = c
        counts = solve_ilp(items, req, alpha)
        if best is None:
            assert counts is None
            continue
        _check_solution(items, counts, req, alpha, best)


def test_engine_matches_pulp():
    pytest.importorskip("pulp")
    rng = np.random.default_rng(11)
    for _ in range(10):
        items = _random_market(rng, max_items=8)
        req = int(rng.integers(1, 50))
        alpha = float(rng.uniform(0, 1))
        counts = solve_ilp(items, req, alpha)
        pulp_counts = solve_ilp_pulp(items, req, alpha)
        assert (counts is None) == (pulp_counts is None)
        if counts is not None:
            assert _objective(items, counts, alpha) == pytest.approx(
                _objective(items, pulp_counts, alpha), abs=1e-6)


# ---------------------------------------------------------- GSS rewire ----

def test_bracketed_gss_identical_before_after_rewire(catalog):
    """The engine path must return pools with identical E_Total to the seed
    per-α path across the paper's scenario grid."""
    for pods, cpu, mem in [(10, 1, 2), (100, 2, 2), (400, 1, 4),
                           (1000, 1, 4), (287, 1, 6)]:
        req = Request(pods=pods, cpu_per_pod=cpu, mem_per_pod=mem)
        items = preprocess(catalog, req)
        engine_pool, engine_trace = bracketed_gss(items, pods, tolerance=0.01)
        legacy_pool, legacy_trace = guarded_gss_reference(
            items, pods, tolerance=0.01, prescan=9,
            solver=solve_ilp_reference)
        assert engine_trace.ilp_solves == legacy_trace.ilp_solves
        assert e_total(engine_pool, pods) == pytest.approx(
            e_total(legacy_pool, pods), rel=1e-9)


def test_pure_gss_identical_before_after_rewire(catalog):
    req = Request(pods=150, cpu_per_pod=2, mem_per_pod=2)
    items = preprocess(catalog, req)
    engine_pool, _ = golden_section_search(items, 150, tolerance=0.01)
    legacy_pool, _ = golden_section_search(items, 150, tolerance=0.01,
                                           solver=solve_ilp_reference)
    assert e_total(engine_pool, 150) == pytest.approx(
        e_total(legacy_pool, 150), rel=1e-9)


def test_provision_identical_before_after_rewire(catalog):
    """KubePACSProvisioner.provision == seed pipeline (preprocess → legacy
    bracketed GSS) on E_Total."""
    prov = KubePACSProvisioner()
    for pods, cpu, mem in [(60, 2, 2), (400, 1, 4)]:
        req = Request(pods=pods, cpu_per_pod=cpu, mem_per_pod=mem)
        d = prov.provision(req, catalog)
        items = preprocess(catalog, req)
        legacy_pool, _ = guarded_gss_reference(
            items, pods, tolerance=0.01, prescan=9,
            solver=solve_ilp_reference)
        assert d.metrics["e_total"] == pytest.approx(
            e_total(legacy_pool, pods), rel=1e-9)


def test_compiled_market_cached_across_reoptimization(catalog):
    """§4.1 re-optimisation (same snapshot, shortfall demand) must reuse the
    compiled market instead of re-running preprocessing."""
    from repro.core import InterruptEvent
    prov = KubePACSProvisioner()
    req = Request(pods=80, cpu_per_pod=2, mem_per_pod=2)
    d1 = prov.provision(req, catalog)
    market_1 = prov._market
    assert market_1 is not None
    victim = d1.pool.items[0].offering.offering_id
    prov.enqueue([InterruptEvent(time=0.0, offering_id=victim, count=1)])
    d2 = prov.handle_interrupts(req, catalog, surviving_pods=30)
    assert d2 is not None
    assert prov._market is market_1          # cache hit: no recompilation
    assert victim not in {it.offering.offering_id for it in d2.pool.items}
    assert d2.pool.total_pods >= 50


def test_exclusion_mask_matches_rebuilt_market():
    """Solving with an exclude mask ≡ rebuilding the candidate set without
    the excluded offerings (incl. the Perf_min/SP_min renormalization)."""
    rng = np.random.default_rng(5)
    for _ in range(20):
        items = _random_market(rng, max_items=8)
        if len(items) < 2:
            continue
        excl = np.zeros(len(items), dtype=bool)
        excl[rng.integers(0, len(items))] = True
        survivors = [it for it, e in zip(items, excl) if not e]
        req = int(rng.integers(0, 30))
        alpha = float(rng.uniform(0, 1))
        masked = solve_ilp(items, req, alpha, market=compile_market(items),
                           exclude=excl)
        rebuilt = solve_ilp(survivors, req, alpha)
        if rebuilt is None:
            assert masked is None
            continue
        assert [c for c, e in zip(masked, excl) if not e] is not None
        assert _objective(survivors,
                          [c for c, e in zip(masked, excl) if not e],
                          alpha) == pytest.approx(
            _objective(survivors, rebuilt, alpha), abs=1e-8)
        assert all(c == 0 for c, e in zip(masked, excl) if e)


# ----------------------------------------------------- batch scoring ----

def test_e_total_batch_matches_scalar(items_100):
    from repro.core import NodePool
    items = items_100[:40]
    rng = np.random.default_rng(0)
    counts = rng.integers(0, 4, size=(16, len(items)))
    perf, price, pods = pool_metric_arrays(items)
    batch = e_total_batch(perf, price, pods, counts, 60)
    for row, score in zip(counts, batch):
        pool = NodePool(items=list(items), counts=[int(c) for c in row])
        assert score == pytest.approx(e_total(pool, 60), rel=1e-12)


# ----------------------------------------------------- memory flatness ----

def test_solver_memory_flat():
    """Peak solver allocation must no longer scale as bundles × demand: the
    seed history matrix alone is ≈ n_bundles × R × 8 bytes, while the
    engine's working set is O(bundles + R)."""
    import tracemalloc
    rng = np.random.default_rng(1)
    items = [_mk_item(i, int(rng.integers(1, 4)), float(rng.uniform(1e3, 1e5)),
                      float(rng.uniform(0.5, 3.0)), int(rng.integers(10, 50)))
             for i in range(150)]
    req = 4000
    market = compile_market(items)
    alpha = 0.02          # low α: the residual DP is the dominant phase
    solve_ilp(items, req, alpha, market=market)   # warm up

    tracemalloc.start()
    counts, stats = solve_ilp(items, req, alpha, market=market,
                              return_stats=True)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert counts is not None and stats.residual_demand > 0
    history_bytes = market.n_bundles * (stats.residual_demand + 1) * 8
    assert peak < history_bytes / 4   # far below the seed's history matrix


def test_lp_prune_preserves_optimum():
    """Pruned bundle sets must still contain an optimal solution."""
    rng = np.random.default_rng(9)
    for _ in range(30):
        B = int(rng.integers(3, 40))
        bpods = rng.integers(1, 12, size=B)
        bcosts = rng.integers(0, 5 << 30, size=B)     # exact int64 costs
        target = int(rng.integers(1, int(bpods.sum()) + 1))
        keep = _lp_prune(bpods, bcosts, target)
        from repro.core.ilp import _cover_dp
        full = _cover_dp(bpods, bcosts, target)[target]
        pruned = _cover_dp(bpods[keep], bcosts[keep], target)[target]
        assert pruned == full
