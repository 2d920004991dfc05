"""ILP solver: exactness (vs PuLP/CBC and brute force), invariants."""

import itertools

import numpy as np
import pytest

from tests._optional import given, settings, st
from tests.oracles import solve_ilp_pulp
from tests.strategies import item_strategy, mk_item

from repro.core import objective_coefficients, solve_ilp


def _brute_force(items, req, alpha):
    coef = objective_coefficients(items, alpha)
    best, best_x = None, None
    ranges = [range(it.t3 + 1) for it in items]
    for xs in itertools.product(*ranges):
        if sum(x * it.pods for x, it in zip(xs, items)) < req:
            continue
        c = float(np.dot(coef, xs))
        if best is None or c < best - 1e-12:
            best, best_x = c, xs
    return best


@settings(max_examples=40, deadline=None)
@given(st.lists(item_strategy, min_size=1, max_size=4),
       st.integers(0, 12), st.floats(0.0, 1.0))
def test_dp_matches_brute_force(items, req, alpha):
    counts = solve_ilp(items, req, alpha)
    expected = _brute_force(items, req, alpha)
    if expected is None:
        assert counts is None
        return
    assert counts is not None
    coef = objective_coefficients(items, alpha)
    got = float(np.dot(coef, counts))
    assert got <= expected + 1e-9
    assert sum(c * it.pods for c, it in zip(counts, items)) >= req


def test_dp_matches_brute_force_deterministic():
    """Seeded twin of the hypothesis property above: always runs, so the
    brute-force exactness check never rides on an optional dependency."""
    rng = np.random.default_rng(101)
    n_feasible = n_infeasible = 0
    for _ in range(60):
        items = [mk_item(i, int(rng.integers(1, 9)),
                         float(rng.uniform(1e3, 1e5)),
                         float(rng.uniform(0.01, 3.0)),
                         int(rng.integers(0, 7)))
                 for i in range(int(rng.integers(1, 5)))]
        req = int(rng.integers(0, 13))
        alpha = float(rng.choice([0.0, 1.0, rng.uniform(0, 1)]))
        counts = solve_ilp(items, req, alpha)
        expected = _brute_force(items, req, alpha)
        if expected is None:
            assert counts is None
            n_infeasible += 1
            continue
        n_feasible += 1
        coef = objective_coefficients(items, alpha)
        assert float(np.dot(coef, counts)) <= expected + 1e-9
        assert sum(c * it.pods for c, it in zip(counts, items)) >= req
    assert n_feasible >= 20 and n_infeasible >= 1


@settings(max_examples=15, deadline=None)
@given(st.lists(item_strategy, min_size=2, max_size=12),
       st.integers(1, 60), st.floats(0.0, 1.0))
def test_dp_matches_pulp(items, req, alpha):
    pytest.importorskip("pulp")
    counts = solve_ilp(items, req, alpha)
    pulp_counts = solve_ilp_pulp(items, req, alpha)
    coef = objective_coefficients(items, alpha)
    if counts is None:
        # CBC reports infeasible too (no feasible integral point)
        cap = sum(it.pods * it.t3 for it in items)
        assert cap < req
        return
    assert pulp_counts is not None
    assert float(np.dot(coef, counts)) == pytest.approx(
        float(np.dot(coef, pulp_counts)), abs=1e-6)


def test_bounds_respected(items_100):
    counts = solve_ilp(items_100[:200], 500, 0.4)
    for c, it in zip(counts, items_100[:200]):
        assert 0 <= c <= it.t3


def test_alpha_one_saturates(items_100):
    """α=1: every positive-perf item has a negative coefficient and is taken
    at its T3 bound — the Table 2 over-provisioning collapse."""
    items = items_100[:100]
    counts = solve_ilp(items, 10, 1.0)
    for c, it in zip(counts, items):
        if it.perf > 0 and it.t3 > 0:
            assert c == it.t3


def test_alpha_zero_minimizes_cost(items_100):
    pytest.importorskip("pulp")
    items = items_100[:60]
    counts = solve_ilp(items, 40, 0.0)
    cost = sum(c * it.spot_price for c, it in zip(counts, items))
    pulp_counts = solve_ilp_pulp(items, 40, 0.0)
    pulp_cost = sum(c * it.spot_price for c, it in zip(pulp_counts, items))
    assert cost == pytest.approx(pulp_cost, rel=1e-6)


def test_infeasible_returns_none():
    items = [mk_item(0, pods=1, bs=1e4, sp=0.1, t3=3)]
    assert solve_ilp(items, 10, 0.5) is None


def test_empty_items():
    assert solve_ilp([], 5, 0.5) is None
    assert solve_ilp([], 0, 0.5) == []
