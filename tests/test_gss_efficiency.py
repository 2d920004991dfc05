"""GSS (Alg. 1 / Eq. 6–7), efficiency metrics (Eq. 1–3), scaling (Eq. 8)."""

import math

import numpy as np
import pytest

from tests._optional import given, settings, st

from repro.core import (CandidateItem, NodePool, Offering, Request,
                        build_base_price_index, e_over_pods, e_perf_cost,
                        e_total, expected_iterations, generate_catalog,
                        golden_section_search, pods_per_instance,
                        scaled_benchmark_score, preprocess)
from repro.core.efficiency import (nonzero_pool, pool_metric_arrays,
                                   score_counts_batch)
from repro.core.gss import PHI, bracketed_gss
from tests.strategies import mk_item as _mk_item


# ---------------------------------------------------------------- GSS ----

def test_expected_iterations_eq7():
    """Eq. 7: k ≈ 4.784·n + 1 for ε = 10⁻ⁿ."""
    for n in (1, 2, 3, 4):
        k = expected_iterations(10.0 ** -n)
        assert k == math.ceil(-n * math.log(10) / math.log(PHI)) + 1
        assert abs(k - (4.784 * n + 1)) <= 1.5


def test_gss_finds_unimodal_peak():
    """Mock solver: E_Total(α) peaked at α*=0.3; GSS must land within ε."""
    peak = 0.3

    def mock_solver(items, req, alpha):
        # one item; count encodes f(α) via perf-cost: pods exactly req
        score = 1000.0 * math.exp(-30 * (alpha - peak) ** 2)
        it = _mk_item(0, pods=req, bs=score, sp=1.0, t3=5)
        items[0] = it          # mutate the placeholder the pool will carry
        return [1]

    items = [_mk_item(0, pods=10, bs=1.0, sp=1.0, t3=5)]
    pool, trace = golden_section_search(items, 10, tolerance=0.005,
                                        solver=mock_solver)
    assert pool is not None
    assert abs(pool.alpha - peak) < 0.02
    # one ILP solve per iteration after the two initial points
    assert trace.ilp_solves <= expected_iterations(0.005) + 3


def test_gss_ilp_solve_count_scales_with_tolerance(items_100):
    items = items_100[:150]
    _, t1 = golden_section_search(items, 30, tolerance=0.1)
    _, t2 = golden_section_search(items, 30, tolerance=0.001)
    assert t2.ilp_solves > t1.ilp_solves
    assert t2.ilp_solves <= expected_iterations(0.001) + 3


def test_bracketed_not_worse_than_pure(items_100):
    items = items_100[:300]
    p1, _ = golden_section_search(items, 50, tolerance=0.01)
    p2, _ = bracketed_gss(items, 50, tolerance=0.01)
    assert e_total(p2, 50) >= e_total(p1, 50) - 1e-9


# ------------------------------------------------------- efficiency ----

def test_pods_per_instance_eq1():
    o = Offering("x@a", "x", "m", 6, "i", "general", "xlarge", "r", "a",
                 vcpus=4, mem_gib=16.0, od_price=0.2, spot_price=0.05,
                 bs_core=2e4, sps_single=3, t3=10, interruption_freq=0)
    assert pods_per_instance(o, Request(pods=1, cpu_per_pod=1, mem_per_pod=2)) == 4
    assert pods_per_instance(o, Request(pods=1, cpu_per_pod=2, mem_per_pod=2)) == 2
    assert pods_per_instance(o, Request(pods=1, cpu_per_pod=1, mem_per_pod=9)) == 1
    assert pods_per_instance(o, Request(pods=1, cpu_per_pod=8, mem_per_pod=1)) == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 6), st.floats(1e3, 1e5),
                          st.floats(0.01, 2.0), st.integers(1, 10),
                          st.integers(0, 5)),
                min_size=1, max_size=6),
       st.integers(1, 40))
def test_e_metrics_invariants(raw, req):
    items = [_mk_item(i, p, bs, sp, t3) for i, (p, bs, sp, t3, _) in
             enumerate(raw)]
    counts = [min(t3, c) for (_, _, _, t3, c) in raw]
    pool = NodePool(items=items, counts=counts)
    if pool.total_pods >= req and pool.total_pods > 0:
        assert 0 < e_over_pods(pool, req) <= 1.0
        assert e_total(pool, req) == pytest.approx(
            e_perf_cost(pool) * e_over_pods(pool, req))
    else:
        assert e_total(pool, req) == 0.0


def test_e_metrics_invariants_deterministic():
    """Seeded twin of the hypothesis property above — the E-metric
    invariants (Eq. 2–3 ranges and the E_Total factorization) hold on
    every randomized pool, optional dependencies or not."""
    rng = np.random.default_rng(73)
    n_covered = n_short = 0
    for _ in range(60):
        raw = [(int(rng.integers(1, 7)), float(rng.uniform(1e3, 1e5)),
                float(rng.uniform(0.01, 2.0)), int(rng.integers(1, 11)),
                int(rng.integers(0, 6)))
               for _ in range(int(rng.integers(1, 7)))]
        req = int(rng.integers(1, 41))
        items = [_mk_item(i, p, bs, sp, t3) for i, (p, bs, sp, t3, _) in
                 enumerate(raw)]
        counts = [min(t3, c) for (_, _, _, t3, c) in raw]
        pool = NodePool(items=items, counts=counts)
        if pool.total_pods >= req and pool.total_pods > 0:
            n_covered += 1
            assert 0 < e_over_pods(pool, req) <= 1.0
            assert e_total(pool, req) == pytest.approx(
                e_perf_cost(pool) * e_over_pods(pool, req))
        else:
            n_short += 1
            assert e_total(pool, req) == 0.0
    assert n_covered >= 10 and n_short >= 10


def test_scores_of_sparse_pools_equal_dense_scores():
    """The fused record's representation scores bit for bit as the dense
    one (DESIGN.md §13): ``e_total`` of ``nonzero_pool`` (only the
    non-zero entries) equals ``e_total`` of the whole-market pool, and
    ``score_counts_batch`` over an int32 array equals it over the same
    rows as int lists."""
    rng = np.random.default_rng(83)
    n = 300
    items = [_mk_item(i, int(rng.integers(1, 9)),
                      float(rng.uniform(1e3, 1e5)),
                      float(rng.uniform(0.01, 3.0)), 40) for i in range(n)]
    arrays = pool_metric_arrays(items)
    n_covered = 0
    for _ in range(40):
        rows = np.zeros((9, n), np.int32)
        for r in range(9):
            nz = rng.choice(n, int(rng.integers(0, 6)), replace=False)
            rows[r, nz] = rng.integers(1, 40, len(nz))
        req = int(rng.integers(1, 200))
        for row in rows:
            dense = NodePool(items=list(items), counts=row.tolist())
            sparse = nonzero_pool(items, row)
            assert sparse.counts == [c for c in row.tolist() if c]
            assert all(type(c) is int for c in sparse.counts)
            assert e_total(sparse, req) == e_total(dense, req)
            assert sparse.total_pods == dense.total_pods
            n_covered += dense.total_pods >= req > 0
        feasible = [None if r % 4 == 3 else row for r, row in enumerate(rows)]
        as_lists = [None if c is None else c.tolist() for c in feasible]
        assert (score_counts_batch(items, feasible, req, arrays=arrays)
                == score_counts_batch(items, as_lists, req, arrays=arrays))
        assert (score_counts_batch(items, rows, req, arrays=arrays)
                == score_counts_batch(items, rows.tolist(), req,
                                      arrays=arrays))
    assert n_covered >= 50


def test_e_total_scale_free_for_single_type():
    """Aggregate/aggregate reading: duplicating a homogeneous pool must not
    change E_PerfCost (and only over-pods penalizes it)."""
    it = _mk_item(0, pods=2, bs=2e4, sp=0.5, t3=50)
    p1 = NodePool(items=[it], counts=[5])
    p2 = NodePool(items=[it], counts=[10])
    assert e_perf_cost(p1) == pytest.approx(e_perf_cost(p2))


# ------------------------------------------------------- Eq. 8 scaling ----

def test_workload_scaling_eq8(catalog):
    idx = build_base_price_index(catalog)
    net = next(o for o in catalog if o.specialization == "network"
               and o.base_instance_type in idx)
    disk = next(o for o in catalog if o.specialization == "disk"
                and o.base_instance_type in idx)
    gen = next(o for o in catalog if o.specialization == "general")

    # network intent: network instances scaled by OP_i/OP_base, disk NOT
    scaled = scaled_benchmark_score(net, {"network"}, idx)
    assert scaled == pytest.approx(
        net.bs_core * net.od_price / idx[net.base_instance_type])
    assert scaled > net.bs_core                       # price premium > 1
    assert scaled_benchmark_score(disk, {"network"}, idx) == disk.bs_core
    assert scaled_benchmark_score(gen, {"network"}, idx) == gen.bs_core
    # no intent: nothing scales
    assert scaled_benchmark_score(net, set(), idx) == net.bs_core
    # dual-intent instances match either
    nd = next((o for o in catalog if o.specialization == "network+disk"
               and o.base_instance_type in idx), None)
    if nd is not None:
        assert scaled_benchmark_score(nd, {"disk"}, idx) > nd.bs_core


def test_preprocess_filters(catalog):
    req = Request(pods=10, cpu_per_pod=2, mem_per_pod=2)
    items = preprocess(catalog, req, excluded={catalog[0].offering_id})
    ids = {it.offering.offering_id for it in items}
    assert catalog[0].offering_id not in ids
    for it in items:
        assert it.pods >= 1 and it.t3 >= 1 and it.spot_price > 0
