"""Exact integer arithmetic of the decision plane (repro.core.exact): the
coefficient formula is exact in int64, tracks the float Eq. 4–5 objective
within its quantum, the golden update stays on the dyadic grid, and the
chosen scale keeps every cost sum inside int64."""

import numpy as np
import pytest

from repro.core import compile_market, exact

from .strategies import big_market, random_exclude, random_market


def test_coefficients_exact_against_python_integers():
    rng = np.random.default_rng(0)
    k = rng.integers(0, exact.ALPHA_ONE + 1, 2000)
    k[:2] = (0, exact.ALPHA_ONE)
    w = rng.integers(0, 1 << 42, 2000)
    q = rng.integers(0, 1 << 42, 2000)
    got = exact.coefficients(k, w, q)
    want = [int(qi) - (int(ki) * int(wi)) // exact.ALPHA_ONE
            for ki, wi, qi in zip(k, w, q)]
    assert got.dtype == np.int64 and got.tolist() == want


def test_int_coefficients_track_float_objective():
    """|C / 2**F − coef(α)| ≤ 2.5 quanta for every live item, mask and
    α; excluded and non-structural items carry zero coefficients."""
    rng = np.random.default_rng(1)
    for _ in range(60):
        items = random_market(rng)
        market = compile_market(items)
        mask = random_exclude(rng, len(items))
        alphas = [0.0, 1.0] + list(rng.uniform(0, 1, 4))
        ks = [exact.alpha_k(a) for a in alphas]
        ints, live = market.int_coefficients(ks, mask)
        floats = market.coefficients([exact.k_alpha(k) for k in ks], mask)
        quantum = 2.0 ** -market.scale_bits
        assert np.all(np.abs(ints * quantum - floats)[:, live]
                      <= 2.5 * quantum)
        assert not np.any(ints[:, ~live])       # never-selected: zeros
        expect = market.structural & (True if mask is None else ~mask)
        assert np.array_equal(live, expect)


def test_golden_grid_arithmetic():
    grid = exact.alpha_grid(9)
    assert [exact.k_alpha(k) for k in grid] == [i / 8 for i in range(9)]
    phi = exact.PHI_Q / (1 << exact.PHI_BITS)
    assert abs(phi - (5 ** 0.5 - 1) / 2) < 2 ** -21
    for d in (1, 2, 3, 1000, exact.ALPHA_ONE):
        w = exact.golden_width(d)
        assert 0 <= w <= phi * d and w < d
    assert exact.alpha_k(exact.k_alpha(123456789)) == 123456789
    t = exact.tolerance_k(0.01)
    assert (t / exact.ALPHA_ONE <= 0.01) and ((t + 1) / exact.ALPHA_ONE
                                             > 0.01)
    with pytest.raises(ValueError):
        exact.alpha_k(1.5)


def test_scale_bits_keeps_sums_in_int64():
    """A deep market (≈10⁶ nodes) still gets ≥ 16 fraction bits, the
    worst cost sum stays below 2**60 and W below the limb bound; a market
    too wide to represent is refused instead of overflowing."""
    market = compile_market(big_market(np.random.default_rng(2)))
    w, q, _active = market.solve_inputs()
    nodes = int(np.sum(market.bound[market.structural]))
    assert market.scale_bits >= exact.MIN_SCALE_BITS
    assert int(w.max()) < 1 << 42
    assert int(q.max()) * nodes < 1 << 60
    with pytest.raises(ValueError, match="too wide"):
        exact.scale_bits(1e12, 10 ** 9)
