"""Solver-backend layer (DESIGN.md §12–13): numpy ≡ the fused device plane
at the level of *selected pools*, cross-decision batching ≡ per-decision
solving, the collect-then-solve fleet tick phase ≡ the sequential one, the
loud failures that replaced every silent fallback, the compile-cache
placement, and the heterogeneous-demand jitter contract.
"""

import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import (NumpyBackend, Request,
                        compile_market, preprocess, generate_catalog,
                        make_backend, objective_coefficients,
                        solve_ilp_batch, solve_ilp_many)
from repro.core import backend as backend_mod
from repro.core.gss import bracketed_gss, bracketed_gss_many
from repro.sim import (ClusterSim, FleetSim, run_replicas,
                       heterogeneous_demand_scenario)

from ._optional import HAVE_JAX, requires_jax
from .strategies import mk_item as _mk_item
from .strategies import random_exclude as _random_exclude
from .strategies import random_market as _random_market

NUMPY = NumpyBackend()
FUSED = make_backend("jax:fused") if HAVE_JAX else None


# -------------------------------------------------- numpy ≡ device plane ----

@requires_jax
def test_jax_backend_on_real_catalog_cycle():
    """A full guarded-GSS cycle on a generated catalog returns the same
    pool and trace through the NumPy engine and the fused device plane."""
    cat = generate_catalog(seed=3, max_offerings=150)
    items = preprocess(cat, Request(pods=800, cpu_per_pod=2, mem_per_pod=2))
    market = compile_market(items)
    fake = lambda: 0.0                                     # noqa: E731
    (pn, tn), = bracketed_gss_many(items, [800], market=market, timer=fake,
                                   backend=NUMPY)
    (pj, tj), = bracketed_gss_many(items, [800], market=market, timer=fake,
                                   backend=FUSED)
    assert pn.as_dict() == pj.as_dict() and pn.alpha == pj.alpha
    assert tn.alphas == tj.alphas and tn.e_totals == tj.e_totals


# ------------------------------------------------- cross-decision batch ----

def test_solve_ilp_many_equals_per_decision_batches():
    """solve_ilp_many over heterogeneous (demand, α grid, mask) decisions
    returns exactly the per-decision solve_ilp_batch results."""
    rng = np.random.default_rng(23)
    for _ in range(25):
        items = _random_market(rng, max_items=10)
        market = compile_market(items)
        n_dec = int(rng.integers(1, 6))
        reqs = [int(rng.integers(0, 70)) for _ in range(n_dec)]
        grids = [[0.0, 1.0] + [float(a) for a in rng.uniform(0, 1, size=2)]
                 for _ in range(n_dec)]
        excludes = [_random_exclude(rng, len(items)) for _ in range(n_dec)]
        many = solve_ilp_many(items, reqs, grids, market=market,
                              excludes=excludes, backend=NUMPY)
        per = [solve_ilp_batch(items, r, g, market=market, exclude=e,
                               backend=NUMPY)
               for r, g, e in zip(reqs, grids, excludes)]
        assert many == per


def test_solve_ilp_many_shared_grid_and_stats():
    items = _random_market(np.random.default_rng(1), max_items=8)
    market = compile_market(items)
    many, stats = solve_ilp_many(items, [10, 25], [0.0, 0.5, 1.0],
                                 market=market, return_stats=True)
    assert len(many) == 2 and all(len(row) == 3 for row in many)
    for d, req in enumerate([10, 25]):
        for a, alpha in enumerate([0.0, 0.5, 1.0]):
            counts = many[d][a]
            if counts is None:
                assert not np.isfinite(stats[d][a].objective)
                continue
            obj = float(np.dot(objective_coefficients(items, alpha), counts))
            assert stats[d][a].objective == pytest.approx(obj, abs=1e-8)
            assert sum(c * it.pods for c, it in zip(counts, items)) >= req


def test_bracketed_gss_many_equals_sequential():
    """Lockstep batched GSS ≡ sequential bracketed_gss per decision:
    pools, α*, and full trace content."""
    cat = generate_catalog(seed=7, max_offerings=120)
    items = preprocess(cat, Request(pods=300, cpu_per_pod=2, mem_per_pod=2))
    market = compile_market(items)
    rng = np.random.default_rng(2)
    reqs = [int(300 * (1 + 0.2 * (2 * rng.random() - 1))) for _ in range(7)]
    excludes = [None, None, *(_random_exclude(rng, len(items))
                              for _ in range(5))]
    fake = lambda: 0.0                                     # noqa: E731
    seq = [bracketed_gss(items, r, market=market, exclude=e, timer=fake)
           for r, e in zip(reqs, excludes)]
    many = bracketed_gss_many(items, reqs, market=market, excludes=excludes,
                              timer=fake)
    for (p1, t1), (p2, t2) in zip(seq, many):
        assert (p1 is None) == (p2 is None)
        if p1 is not None:
            assert p1.as_dict() == p2.as_dict() and p1.alpha == p2.alpha
        assert t1.alphas == t2.alphas
        assert t1.e_totals == t2.e_totals
        assert t1.ilp_solves == t2.ilp_solves


# -------------------------------------------- collect-then-solve fleet ----

def test_fleet_batched_tick_phase_trace_equality():
    """FleetSim with the collect-then-solve batch on vs off: byte-identical
    JSONL traces on the heterogeneous-demand scenario (low memo-hit) and on
    a deterministic-storm scenario (high memo-hit)."""
    from repro.risk import backtest
    seeds = [0, 1, 2]
    for sc in (heterogeneous_demand_scenario(duration_hours=24.0,
                                             max_offerings=80),
               backtest.interrupt_storm_scenario(duration_hours=24.0,
                                                 max_offerings=80)):
        on = FleetSim(sc, seeds, record_traces=True).run()
        off = FleetSim(sc, seeds, record_traces=True,
                       batch_decisions=False).run()
        for a, b in zip(on, off):
            assert a.recorder.dumps() == b.recorder.dumps()


def test_fleet_batched_memo_counters_match_sequential():
    """Duplicate pending keys count as memo hits, so the PR 4 counter
    semantics survive batching (8 identical storm replicas → 1 miss +
    7 hits per decision event)."""
    from repro.risk import backtest
    sc = backtest.interrupt_storm_scenario(duration_hours=24.0,
                                           max_offerings=80)
    on = FleetSim(sc, list(range(8)))
    on.run()
    off = FleetSim(sc, list(range(8)), batch_decisions=False)
    off.run()
    s_on, s_off = on.stats(), off.stats()
    for k in ("memo_hits", "memo_misses", "memo_unique_solves"):
        assert s_on[k] == s_off[k]
    assert s_on["memo_hits"] == 7 * s_on["memo_misses"]


def test_fleet_hetero_matches_standalone_bit_for_bit():
    """Heterogeneous-demand: every fleet replica (batched) is identical to
    a standalone ClusterSim at the same seed — traces and float totals."""
    sc = heterogeneous_demand_scenario(duration_hours=24.0, max_offerings=80)
    seeds = [0, 1, 2]
    fleet = FleetSim(sc, seeds, record_traces=True).run()
    per_seed = run_replicas(sc, seeds)
    for seed, f, p in zip(seeds, fleet, per_seed):
        single = ClusterSim(
            dataclasses.replace(sc, interrupt_seed=seed)).run()
        assert f.recorder.dumps() == single.recorder.dumps()
        assert f.total_cost == single.total_cost
        assert f.total_perf_hours == single.total_perf_hours
        assert f.decision_records() == p.decision_records()


def test_fleet_hetero_defeats_memo():
    """The scenario does its job: per-replica jitter drives the memo hit
    rate below 50 % (the regime the batched tick phase targets)."""
    sc = heterogeneous_demand_scenario(duration_hours=24.0, max_offerings=80)
    sim = FleetSim(sc, list(range(8)))
    sim.run()
    stats = sim.stats()
    lookups = stats["memo_hits"] + stats["memo_misses"]
    assert lookups > 0
    assert stats["memo_hits"] / lookups < 0.5


# ------------------------------------------------ demand-jitter contract ----

def test_effective_pods_deterministic_and_seed_dependent():
    sc = heterogeneous_demand_scenario()
    a = sc.effective_pods(3, 6.0, 220)
    assert a == sc.effective_pods(3, 6.0, 220)         # pure function
    assert a != 220 or sc.effective_pods(4, 6.0, 220) != 220
    vals = {sc.effective_pods(s, 6.0, 220) for s in range(16)}
    assert len(vals) > 8                               # replicas diverge
    assert all(1 <= v <= 220 * 1.2 for v in vals)
    zero = dataclasses.replace(sc, demand_jitter=0.0)
    assert zero.effective_pods(3, 6.0, 220) == 220     # exact passthrough


def test_scenario_roundtrip_keeps_jitter():
    sc = heterogeneous_demand_scenario()
    from repro.sim import Scenario
    assert Scenario.from_dict(sc.to_dict()) == sc
    # pre-jitter trace headers (no key) still load
    d = sc.to_dict()
    del d["demand_jitter"]
    assert Scenario.from_dict(d).demand_jitter == 0.0


def test_jitter_replay_reproduces_decisions():
    """A recorded heterogeneous-demand trace replays to the identical
    decision sequence (jitter is re-derived from the header scenario)."""
    sc = heterogeneous_demand_scenario(duration_hours=18.0, max_offerings=60)
    res = ClusterSim(sc).run()
    replay = ClusterSim.replay(res.records).run()
    assert res.decision_records() == replay.decision_records()


# --------------------------------------------------------- loud failures ----

def test_jax_spec_without_jax_raises(monkeypatch):
    """Requesting the device plane without jax installed fails loudly —
    no NumPy stand-in, no warning — while core/ilp.py never imports jax
    itself and the numpy spec still builds."""
    import builtins
    real_import = builtins.__import__

    def no_jax(name, *args, **kwargs):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("no jax in this environment")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_jax)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(ImportError, match="no jax"):
            backend_mod.make_backend("jax:fused")
        assert isinstance(backend_mod.make_backend("numpy"), NumpyBackend)
    for gone in ("jax", "jax:pallas", "jax:fused:pallas"):
        with pytest.raises(ValueError, match="unknown solver backend"):
            backend_mod.make_backend(gone)


def test_env_selects_default_backend(monkeypatch):
    monkeypatch.setenv("KUBEPACS_SOLVER_BACKEND", "numpy")
    backend_mod.set_backend(None)
    try:
        assert isinstance(backend_mod.get_backend(), NumpyBackend)
        with pytest.raises(ValueError, match="unknown solver backend"):
            backend_mod.make_backend("torch")
    finally:
        backend_mod.set_backend("numpy")


def test_solver_core_importable_without_jax(monkeypatch):
    """repro.core.ilp/gss must not import jax at module import time: their
    modules never hold a jax attribute."""
    import repro.core.ilp as ilp_mod
    import repro.core.gss as gss_mod
    for mod in (ilp_mod, gss_mod, backend_mod):
        assert not hasattr(mod, "jax")
        src = open(mod.__file__).read().splitlines()
        assert not any(line.startswith("import jax") for line in src)


@requires_jax
def test_x64_flip_env_opt_out_and_warning():
    """Constructing a jax backend enables jax_enable_x64 process-wide —
    announced by a one-time RuntimeWarning — and KUBEPACS_JAX_X64=0
    forbids the global-config mutation outright (fresh subprocess: this
    process flipped the flag long ago)."""
    import os
    import subprocess
    import sys
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(backend_mod.__file__))))
    code = (
        "import os, warnings\n"
        "os.environ['KUBEPACS_JAX_X64'] = '0'\n"
        "from repro.core import make_backend\n"
        "try:\n"
        "    make_backend('jax:fused')\n"
        "    raise SystemExit('opt-out did not refuse')\n"
        "except RuntimeError as e:\n"
        "    assert 'jax_enable_x64' in str(e)\n"
        "    print('REFUSED')\n"
        "del os.environ['KUBEPACS_JAX_X64']\n"
        "with warnings.catch_warnings(record=True) as w:\n"
        "    warnings.simplefilter('always')\n"
        "    make_backend('jax:fused')\n"
        "assert any('x64' in str(x.message) for x in w)\n"
        "print('WARNED')\n"
    )
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("KUBEPACS_JAX_X64", None)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "REFUSED" in res.stdout and "WARNED" in res.stdout


# ------------------------------------------------- fused decision plane ----

def _gss_summary(results):
    """(pool dict, alpha, trace alphas, trace e_totals) per decision —
    the full byte-comparable decision record."""
    return [((None if p is None else p.as_dict()),
             (None if p is None else p.alpha), t.alphas, t.e_totals)
            for p, t in results]


@requires_jax
def test_fused_equals_numpy_pools_110_markets():
    """The device-resident GSS (one jitted while_loop, counts read back
    once) selects the identical pools/alphas/traces as the host engine
    over 110 randomized markets with masks, infeasible and zero demands —
    and resolves every probe from the device record (zero host-fallback
    solves)."""
    rng = np.random.default_rng(11)
    fake = lambda: 0.0                                     # noqa: E731
    base_fb = FUSED.device_cache_info()["fallback_solves"]
    n_infeasible = n_masked = 0
    for _ in range(110):
        items = _random_market(rng)
        market = compile_market(items)
        reqs = [int(rng.integers(0, 90))
                for _ in range(int(rng.integers(1, 4)))]
        excludes = [_random_exclude(rng, len(items)) for _ in reqs]
        n_masked += sum(e is not None for e in excludes)
        got_n = bracketed_gss_many(items, reqs, market=market,
                                   excludes=excludes, timer=fake,
                                   backend=NUMPY)
        got_f = bracketed_gss_many(items, reqs, market=market,
                                   excludes=excludes, timer=fake,
                                   backend=FUSED)
        assert _gss_summary(got_n) == _gss_summary(got_f)
        n_infeasible += sum(p is None for p, _t in got_n)
    assert n_infeasible > 0 and n_masked > 10
    assert FUSED.device_cache_info()["fallback_solves"] == base_fb


@requires_jax
def test_fused_empty_market_and_zero_demand():
    fake = lambda: 0.0                                     # noqa: E731
    (p0, _t), = bracketed_gss_many([], [0], timer=fake, backend=FUSED)
    assert p0 is not None and p0.as_dict() == {}
    (p1, _t), = bracketed_gss_many([], [5], timer=fake, backend=FUSED)
    assert p1 is None


@requires_jax
def test_fused_device_cache_hit_and_invalidation():
    """CompiledMarket arrays upload once per (digest, pad-shape): a repeat
    dispatch is a cache hit, a changed market (new digest) is a miss, and
    the LRU keeps serving the old entry if it returns."""
    be = make_backend("jax:fused")
    rng = np.random.default_rng(7)
    fake = lambda: 0.0                                     # noqa: E731
    items_a = _random_market(rng, max_items=6)
    items_b = _random_market(rng, max_items=6)
    market_a = compile_market(items_a)
    market_b = compile_market(items_b)
    assert market_a.digest != market_b.digest
    bracketed_gss_many(items_a, [20], market=market_a, timer=fake,
                       backend=be)
    info0 = be.device_cache_info()
    assert info0["misses"] >= 1
    bracketed_gss_many(items_a, [25], market=market_a, timer=fake,
                       backend=be)
    info1 = be.device_cache_info()
    assert info1["hits"] > info0["hits"]          # same digest: resident
    assert info1["misses"] == info0["misses"]
    bracketed_gss_many(items_b, [20], market=market_b, timer=fake,
                       backend=be)
    info2 = be.device_cache_info()
    assert info2["misses"] > info1["misses"]      # new digest: re-upload


@requires_jax
def test_prescan_host_crosscheck_raises_on_divergence():
    """Device prescan counts are never consumed unverified: each batch
    cross-checks one sampled (decision, α) row against the NumPy engine,
    and a mismatch raises — it neither changes a selection nor quietly
    hands the batch to the host.  The backend stays usable."""
    be = make_backend("jax:fused")
    orig = be._run_prescan

    def corrupted(market, reqs, excludes, grid, **kw):
        counts, feas = orig(market, reqs, excludes, grid, **kw)
        counts = np.asarray(counts).copy()
        counts[..., 0] += 1                  # silent device-side corruption
        feas = np.ones_like(np.asarray(feas))
        return counts, feas

    be._run_prescan = corrupted
    rng = np.random.default_rng(41)
    fake = lambda: 0.0                                     # noqa: E731
    items = _random_market(rng, max_items=6)
    market = compile_market(items)
    with pytest.raises(backend_mod.PrescanMismatch, match="diverged"):
        bracketed_gss_many(items, [20], market=market, timer=fake,
                           backend=be)
    assert be.fused_records == 0
    be._run_prescan = orig
    got_f = bracketed_gss_many(items, [25], market=market, timer=fake,
                               backend=be)
    got_n = bracketed_gss_many(items, [25], market=market, timer=fake,
                               backend=NUMPY)
    assert _gss_summary(got_n) == _gss_summary(got_f)
    assert be.fused_records == 1


@requires_jax
def test_fused_device_error_propagates():
    """A failing device program raises out of bracketed_gss_many: no
    except in the plane turns it into a host or per-dispatch solve."""
    be = make_backend("jax:fused")

    def broken(*_a, **_k):
        raise RuntimeError("device program failed")

    be._run_golden = broken
    rng = np.random.default_rng(43)
    items = _random_market(rng, max_items=6)
    with pytest.raises(RuntimeError, match="device program failed"):
        bracketed_gss_many(items, [20], market=compile_market(items),
                           timer=lambda: 0.0, backend=be)
    assert be.fallback_solves == 0 and be.declined_batches == 0


@requires_jax
def test_fused_prescan_rows_equal_numpy_110_markets():
    """The device row solver alone — prescan rows at arbitrary grid
    indices, not only the GSS grid — returns the host engine's exact
    count vectors over 110 randomized markets, masks and demands."""
    from repro.core import exact
    rng = np.random.default_rng(47)
    n_infeasible = 0
    for _ in range(110):
        items = _random_market(rng)
        market = compile_market(items)
        reqs = [int(rng.integers(0, 90))
                for _ in range(int(rng.integers(1, 4)))]
        excludes = [_random_exclude(rng, len(items)) for _ in reqs]
        ks = [0, exact.ALPHA_ONE] + [
            int(k) for k in rng.integers(0, exact.ALPHA_ONE, 3)]
        counts, feas = FUSED._run_prescan(market, reqs, excludes, ks)
        ref = solve_ilp_many(items, reqs,
                             [exact.k_alpha(k) for k in ks],
                             market=market, excludes=excludes,
                             backend=NUMPY)
        for d in range(len(reqs)):
            for g in range(len(ks)):
                got = list(map(int, counts[d, g])) if feas[d, g] else None
                assert got == ref[d][g], (d, g)
                n_infeasible += got is None
    assert n_infeasible > 0


def _sizes_market(rng, n_sizes):
    """A gcd-2 market with exactly ``n_sizes`` distinct bundle pod sizes:
    single-node items of 2, 4, …, 2·n_sizes pods, and three-node items
    whose two bundles (one and two nodes) repeat sizes already there."""
    pods = [2 * i for i in range(1, n_sizes + 1)]
    pods += [2 * int(i) for i in rng.integers(1, n_sizes // 2 + 1, 20)]
    t3 = [1] * n_sizes + [3] * 20
    return [_mk_item(i, p, float(rng.uniform(1e3, 1e5)),
                     float(rng.uniform(0.01, 3.0)), t)
            for i, (p, t) in enumerate(zip(pods, t3))]


@requires_jax
@pytest.mark.parametrize("n_sizes,table", [(128, True), (136, False)],
                         ids=["full_table", "search_fallback"])
def test_fused_lp_prune_equals_numpy_at_the_table_width(n_sizes, table):
    """The LP prune by distinct-size table (a market whose bundle sizes
    fill the table exactly) and by per-bundle search (one size more than
    it holds) select the NumPy engine's pools, α and every probe: rows
    whose need is 0 for every bundle but the smallest, rows whose
    residual equals their capacity (below the coarsening threshold and
    gcd-coarsened above it), a masked row and a gcd-coarsened row."""
    from repro.core import CoarseningConfig
    rng = np.random.default_rng(n_sizes)
    items = _sizes_market(rng, n_sizes)
    market = compile_market(items)
    assert len(np.unique(market.b_pods)) == n_sizes
    assert market.pods_gcd == 2
    capacity = sum(it.pods * it.t3 for it in items)
    # the first 29 single-node items hold 2 + 4 + … + 58 = 870 pods
    small = np.arange(len(items)) >= 29
    reqs = [3, 700, 870, capacity, 5000]
    excludes = [None, _random_exclude(rng, len(items)), small, None, None]
    cfg = CoarseningConfig(threshold=1000, max_rows=100_000)
    be = make_backend("jax:fused")
    fake = lambda: 0.0                                     # noqa: E731
    got_n = bracketed_gss_many(items, reqs, market=market, excludes=excludes,
                               timer=fake, backend=NUMPY, coarsening=cfg)
    got_f = bracketed_gss_many(items, reqs, market=market, excludes=excludes,
                               timer=fake, backend=be, coarsening=cfg)
    assert _gss_summary(got_n) == _gss_summary(got_f)
    assert all(p is not None for p, _t in got_f)
    info = be.device_cache_info()
    assert info["fallback_solves"] == 0 and info["program_builds"] == 2
    assert info["table_prune_programs"] == (2 if table else 0)


def _bench_market(config):
    """The compiled market of a benchmark deployment at catalog seed 11."""
    import json
    import os

    from bench import catalog
    from repro.core import Offering
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    offerings = [Offering(**o)
                 for o in catalog.deployment_offerings(cfg, 11)]
    return compile_market(preprocess(
        offerings, Request(pods=1, cpu_per_pod=cfg["pod_cpu"],
                           mem_per_pod=cfg["pod_mem_gib"])))


@requires_jax
@pytest.mark.parametrize("config,n_sizes", [("karpenter_zone_m", 41),
                                            ("karpenter_region_cmr", 47),
                                            ("karpenter_zone_m_250m", 41)])
def test_benchmark_catalogs_take_the_table_prune(config, n_sizes):
    """Both benchmark deployments (catalog seed 11) have few distinct
    bundle sizes, so every program their ticks build prunes by the
    table; counted by ``device_cache_info()["table_prune_programs"]``."""
    market = _bench_market(config)
    assert len(np.unique(market.b_pods)) == n_sizes
    be = make_backend("jax:fused")
    N, B, RC, D = be._shape_key(market, [1000] * 8, 8)
    _md, table = be._device_market(market, N, B)
    be._prescan_program(N, B, RC, D, 9, table)
    be._golden_program(N, B, RC, D, 12, table)
    info = be.device_cache_info()
    assert info["table_prune_programs"] == info["program_builds"] == 2


@requires_jax
@pytest.mark.parametrize("miss", [False, True])
def test_fused_sparse_wide_market_equals_numpy(miss, monkeypatch):
    """On the region deployment's 2,444 items, where a pool holds a
    handful, the fused record's count arrays and the pools built from
    their non-zero entries (DESIGN.md §13) select bit for bit what the
    NumPy path selects: pools with ``int`` counts,
    α, and traces that serialise byte-identically, with a mask and an
    infeasible decision (every item excluded).  With ``miss`` the first
    golden probe's lookup entry is dropped, so one probe takes the host
    fallback through the same representation."""
    import json

    market = _bench_market("karpenter_region_cmr")
    items = market.items
    assert market.n >= 2000
    rng = np.random.default_rng(61)
    mask = np.zeros(market.n, bool)
    mask[rng.choice(market.n, 60, replace=False)] = True
    reqs = [900, 1100, 1000]
    excludes = [None, mask, np.ones(market.n, bool)]
    if miss:
        run_golden = backend_mod._FusedGssRecord.run_golden

        def drop_first_probe(rec, a_list, b_list):
            before = set(rec._lookup[0])
            run_golden(rec, a_list, b_list)
            # the lookup keeps insertion order: the grid, then the probes
            del rec._lookup[0][next(k for k in rec._lookup[0]
                                    if k not in before)]

        monkeypatch.setattr(backend_mod._FusedGssRecord, "run_golden",
                            drop_first_probe)
    be = make_backend("jax:fused")
    fake = lambda: 0.0                                     # noqa: E731
    got_n = bracketed_gss_many(items, reqs, market=market,
                               excludes=excludes, timer=fake, backend=NUMPY)
    got_f = bracketed_gss_many(items, reqs, market=market,
                               excludes=excludes, timer=fake, backend=be)
    assert _gss_summary(got_n) == _gss_summary(got_f)
    assert got_f[2][0] is None and all(p is not None for p, _ in got_f[:2])
    for (_pn, tn), (pf, tf) in zip(got_n, got_f):
        assert (json.dumps(dataclasses.asdict(tn))
                == json.dumps(dataclasses.asdict(tf)))
        if pf is not None:
            assert 0 < len(pf.counts) <= 12
            assert all(type(c) is int and c > 0 for c in pf.counts)
    info = be.device_cache_info()
    assert info["fused_records"] == 1
    assert info["fallback_solves"] == (1 if miss else 0)


@requires_jax
def test_fleet_fused_traces_byte_identical():
    """FleetSim with ``backend="jax:fused"`` (string spec resolved via
    make_backend) produces byte-identical traces, decisions and float
    totals to the default numpy plane, and surfaces the device-cache
    counters in cache_stats."""
    from repro.risk import backtest
    from repro.sim import run_fleet
    sc = backtest.price_shock_scenario(duration_hours=24.0,
                                       max_offerings=60)
    base = run_fleet(sc, [0, 1], record_traces=True)
    fused = run_fleet(sc, [0, 1], record_traces=True,
                      backend="jax:fused")
    for a, b in zip(base, fused):
        assert a.records == b.records
        assert a.total_cost == b.total_cost
        assert a.total_perf_hours == b.total_perf_hours
        assert [(r, d.pool.as_dict(), d.alpha, d.metrics)
                for r, d in a.decisions] == \
               [(r, d.pool.as_dict(), d.alpha, d.metrics)
                for r, d in b.decisions]
    stats = fused[0].cache_stats
    assert stats.get("device_cache_fallback_solves") == 0
    assert stats.get("device_cache_entries", 0) >= 1


# ------------------------------------------------------ compile cache ----

@requires_jax
@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, tmp_path):
    """Compiled device programs persist where JAX_COMPILATION_CACHE_DIR
    says, and at the fixed <checkout>/.jax_cache when it is unset (fresh
    process: the cache directory binds at the first compile)."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(backend_mod.__file__).resolve().parents[3]
    expect = tmp_path / "cache" if from_env else root / ".jax_cache"
    code = (
        "import jax, pathlib\n"
        "from repro.core import make_backend, compile_market\n"
        "from repro.core.gss import bracketed_gss_many\n"
        "from tests.strategies import random_market\n"
        "import numpy as np\n"
        "be = make_backend('jax:fused')\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(pathlib.Path(jax.config.jax_compilation_cache_dir))\n"
        "items = random_market(np.random.default_rng(3), max_items=5)\n"
        "bracketed_gss_many(items, [9], market=compile_market(items),\n"
        "                   backend=be)\n"
    )
    env = dict(os.environ, PYTHONPATH=f"{root / 'src'}:{root}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(expect)
    before = set(expect.iterdir()) if expect.is_dir() else set()
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    assert pathlib.Path(res.stdout.strip().splitlines()[-1]) == expect
    assert backend_mod.compile_cache_dir() == (
        pathlib.Path(os.environ["JAX_COMPILATION_CACHE_DIR"])
        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else root / ".jax_cache")
    after = set(expect.iterdir())
    # a fresh directory gains entries; the shared checkout cache may
    # already hold these programs from an earlier run
    assert (after - before) if from_env else after


#: the named scopes of the device programs' stages (DESIGN.md §13), which
#: the benchmark's trace reduction reads (bench/trace.py STAGES)
_ROW_STAGES = ("saturate", "sort", "lp_prune", "core_dp", "compact",
               "cover_dp", "backtrack", "rows")


def _lower_program(be, program, N, B, RC, D, table=True):
    """One fused program lowered at the given shapes (G = 9, MAXR = 12)."""
    import jax
    import jax.numpy as jnp

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    market = (s((N,), jnp.int32), s((N,), jnp.int32), s((B,), jnp.int32),
              s((B,), jnp.int32), s((B,), jnp.int32), s((B,), bool),
              s((N,), jnp.float32), s((N,), jnp.float32))
    decisions = (s((D, N), jnp.int64), s((D, N), jnp.int64),
                 s((D, N), bool), s((D,), jnp.int64))
    if program == "prescan":
        fn = be._prescan_program(N, B, RC, D, 9, table)
        tail = (s((9,), jnp.int64),)
    else:
        fn = be._golden_program(N, B, RC, D, 12, table)
        tail = (s((D,), jnp.int64), s((D,), jnp.int64), s((), jnp.int64))
    return fn.lower(market, *decisions, *tail, s((3,), jnp.int64))


@requires_jax
@pytest.mark.parametrize("program,stages", [
    ("prescan", _ROW_STAGES), ("golden", _ROW_STAGES + ("score", "control"))])
def test_device_programs_carry_names_and_stage_scopes(program, stages):
    """Both jits carry a stable name of their own, and every stage of the
    row solver (and the golden loop's scoring and control) its named
    scope, so a profiler trace can name each operation's stage."""
    import re

    lowered = _lower_program(make_backend("jax:fused"), program,
                             16, 32, 129, 2)
    assert f"@jit_kubepacs_{program}" in lowered.as_text()
    scopes = set()
    for loc in re.findall(r'loc\("([^"]*)"', lowered.as_text(
            debug_info=True)):
        scopes.update(loc.split("/"))
    assert set(stages) <= scopes
    if program == "prescan":
        assert not {"score", "control"} & scopes


@requires_jax
@pytest.mark.parametrize("program", ["prescan", "golden"])
def test_table_prune_holds_no_per_bundle_search(program):
    """Compiled at zone_m's shapes, the table programs' LP prune holds no
    per-bundle binary search (no ``while`` under
    ``lp_prune/jit(searchsorted)/vmap()``, which the search fallback
    has), and every operation the table adds is under the ``lp_prune``
    scope, so a trace attributes it to that stage."""
    import re

    be = make_backend("jax:fused")
    names = {}
    for table in (True, False):
        text = _lower_program(be, program, 512, 1152, 1537, 32,
                              table).compile().as_text()
        names[table] = set(re.findall(r'op_name="([^"]*)"', text))
    search = "/lp_prune/jit(searchsorted)/vmap()/while"
    assert not [n for n in names[True] if search in n]
    assert [n for n in names[False] if search in n]
    # a bare name is a reducer's body, not an operation of the program
    added = [n for n in names[True] - names[False] if "/" in n]
    assert added and all("/lp_prune/" in n for n in added)
