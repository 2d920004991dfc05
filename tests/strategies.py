"""Shared randomized-market generators for the test suite.

One canonical copy of the ``CandidateItem`` factory and the random-market
samplers that ``test_ilp``, ``test_solver_engine``, ``test_backend``,
``test_gss_efficiency``, and ``test_coarsening`` previously each grew
privately.  Two layers:

* plain callables (``mk_item`` / ``random_market`` / ``random_exclude`` /
  ``gcd_market`` / ``big_market``) — deterministic given an
  ``np.random.Generator``, usable with or without hypothesis;
* hypothesis strategies (``item_strategy`` / ``items_strategy``) built on
  the same factory through :mod:`tests._optional`, so modules import them
  unconditionally and each ``@given`` test skips individually when
  hypothesis is absent.
"""

import numpy as np

from repro.core import CandidateItem, Offering

from ._optional import st


def mk_item(i, pods, bs, sp, t3):
    """One synthetic offering/candidate with the full Offering signature."""
    o = Offering(offering_id=f"t{i}@az", instance_type=f"t{i}", family="m",
                 generation=6, vendor="i", specialization="general",
                 size="large", region="r", az="az", vcpus=2, mem_gib=8.0,
                 od_price=sp * 3, spot_price=sp, bs_core=bs, sps_single=3,
                 t3=t3, interruption_freq=1)
    return CandidateItem(offering=o, pods=pods, bs=bs, spot_price=sp, t3=t3)


def random_market(rng, max_items=12, max_t3=9):
    """The suite's canonical small random market: 1..max_items items,
    pods 1..8, t3 0..max_t3-1 (zero-t3 rows exercise the structural
    mask)."""
    n = int(rng.integers(1, max_items + 1))
    return [mk_item(i, int(rng.integers(1, 9)),
                    float(rng.uniform(1e3, 1e5)),
                    float(rng.uniform(0.01, 3.0)),
                    int(rng.integers(0, max_t3)))
            for i in range(n)]


def random_exclude(rng, n):
    """A ~30% exclusion mask (or None) over an n-item market."""
    if n == 0 or rng.random() < 0.4:
        return None
    mask = rng.random(n) < 0.3
    return mask if mask.any() else None


def gcd_market(rng, n_items=80, pod_mult=8, t3_lo=20, t3_hi=120):
    """A market whose pod counts all share the factor ``pod_mult`` — the
    demand-coarsening gcd tier's natural habitat (DESIGN.md §14)."""
    return [mk_item(i, pod_mult * int(rng.integers(1, 9)),
                    float(rng.uniform(0.5, 4.0)),
                    float(rng.uniform(0.05, 2.5)),
                    int(rng.integers(t3_lo, t3_hi)))
            for i in range(n_items)]


def big_market(rng, n_items=600, t3_lo=200, t3_hi=3000):
    """A deep market (capacity in the millions of pods) for the approx
    coarsening tier tests; gcd is almost surely 1."""
    return [mk_item(i, int(rng.integers(1, 9)),
                    float(rng.uniform(0.5, 4.0)),
                    float(rng.uniform(0.05, 2.5)),
                    int(rng.integers(t3_lo, t3_hi)))
            for i in range(n_items)]


#: hypothesis strategies over the same factory (no-ops without hypothesis —
#: the @given decorator from tests._optional skips those tests individually)
item_strategy = st.builds(
    lambda i, pods, bs, sp, t3: mk_item(i, pods, bs, sp, t3),
    st.integers(0, 10_000), st.integers(1, 8),
    st.floats(1e3, 1e5), st.floats(0.01, 3.0), st.integers(0, 6))


def items_strategy(min_size=1, max_size=8):
    return st.lists(item_strategy, min_size=min_size, max_size=max_size)


__all__ = ["big_market", "gcd_market", "item_strategy", "items_strategy",
           "mk_item", "random_exclude", "random_market"]
