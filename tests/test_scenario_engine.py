"""Scenario engine: determinism/replay contract, interrupt-model semantics,
multi-seed sharing, and scenario-derived benchmark consistency (DESIGN.md §9)."""

import dataclasses

import numpy as np
import pytest

from repro.core import Request, generate_catalog
from repro.sim import (ClusterSim, PriceCrossingInterruptModel,
                       RebalanceRecommendationModel, Scenario, Shock,
                       loads_trace, make_interrupt_model, run_replicas)


def storm_scenario(**overrides) -> Scenario:
    """A 6-round interrupt storm small enough for unit tests."""
    base = dict(name="test_storm", duration_hours=36.0, step_hours=6.0,
                pods=60, cpu_per_pod=2, mem_per_pod=2,
                interrupt_model="pressure", inject_if_idle=True,
                policy="kubepacs", catalog_seed=1, max_offerings=150,
                market_seed=1, interrupt_seed=1)
    base.update(overrides)
    return Scenario(**base)


# ---------------------------------------------------------------- traces ----

def test_same_seed_byte_identical_trace():
    sc = storm_scenario()
    a = ClusterSim(sc).run().recorder.dumps()
    b = ClusterSim(sc).run().recorder.dumps()
    assert a == b


def test_different_interrupt_seed_changes_trace_not_market():
    sc = storm_scenario()
    a = ClusterSim(sc).run()
    b = ClusterSim(dataclasses.replace(sc, interrupt_seed=99)).run()
    assert a.recorder.dumps() != b.recorder.dumps()
    # market evolution is independently seeded: identical price records
    states_a = [r for r in a.records if r["type"] == "market_state"]
    states_b = [r for r in b.records if r["type"] == "market_state"]
    assert [s["spot"] for s in states_a] == [s["spot"] for s in states_b]


def test_replay_reproduces_decisions_and_costs():
    """Acceptance: a recorded 6-round interrupt scenario replays to an
    identical ProvisioningDecision sequence — and an identical trace."""
    sc = storm_scenario()
    live = ClusterSim(sc).run()
    assert len(live.rounds) == 6
    assert any(rd.effective for rd in live.rounds)

    text = live.recorder.dumps()           # JSONL round trip
    replayed = ClusterSim.replay(loads_trace(text)).run()

    assert replayed.decision_records() == live.decision_records()
    assert [(r, d.pool.as_dict(), d.alpha, d.metrics)
            for r, d in replayed.decisions] == \
           [(r, d.pool.as_dict(), d.alpha, d.metrics)
            for r, d in live.decisions]
    assert replayed.total_cost == live.total_cost
    assert replayed.recorder.dumps() == text


def test_replay_with_fulfillment_and_shocks():
    sc = storm_scenario(apply_fulfillment=True, pods=120,
                        demand_schedule=((15.0, 40),),
                        shocks=(Shock(time=9.0, kind="capacity", factor=0.3),
                                Shock(time=21.0, kind="price", factor=2.0,
                                      selector="us-east-1")))
    live = ClusterSim(sc).run()
    replayed = ClusterSim.replay(live.records).run()
    assert replayed.recorder.dumps() == live.recorder.dumps()


def test_replay_needs_no_rng(monkeypatch):
    """Replaying a trace must never draw randomness: the static catalog is
    rebuilt from its seed up front, after which the run is RNG-free."""
    sc = storm_scenario()
    catalog = sc.build_catalog()
    live = ClusterSim(sc, catalog=catalog).run()

    def boom(*a, **k):
        raise AssertionError("replay consumed RNG")
    monkeypatch.setattr(np.random, "default_rng", boom)
    replayed = ClusterSim.replay(live.records, catalog=catalog).run()
    assert replayed.decision_records() == live.decision_records()


def test_replay_rejects_mismatched_catalog():
    """A trace recorded against an explicit catalog must not be silently
    replayed against the catalog regenerated from the Scenario seeds."""
    sc = storm_scenario(duration_hours=6.0)
    other = generate_catalog(seed=42, max_offerings=sc.max_offerings)
    live = ClusterSim(sc, catalog=other).run()   # catalog ≠ scenario seeds
    with pytest.raises(ValueError, match="catalog mismatch"):
        ClusterSim.replay(live.records)          # would rebuild from seeds
    # passing the recording catalog explicitly replays exactly
    rep = ClusterSim.replay(live.records, catalog=other).run()
    assert rep.recorder.dumps() == live.recorder.dumps()


def test_decision_metrics_schema_uniform_across_policies():
    """Every policy (and the infeasible path) emits the same metric keys;
    kubepacs_risk adds exactly its optimized risk score on top."""
    keys = {"e_total", "e_perf_cost", "e_over_pods", "hourly_cost",
            "nodes", "pods"}
    for policy in ("kubepacs", "karpenter_like", "fixed_alpha:0.5"):
        sc = storm_scenario(duration_hours=0.0, policy=policy)
        res = ClusterSim(sc).run()
        assert set(res.decision_records()[0]["metrics"]) == keys
    sc = storm_scenario(duration_hours=0.0, policy="kubepacs_risk:12")
    res = ClusterSim(sc).run()
    assert set(res.decision_records()[0]["metrics"]) == keys | {"e_risk"}
    # infeasible demand: empty pool, same schema, zero scores
    sc = storm_scenario(duration_hours=0.0, pods=10**7)
    rec = ClusterSim(sc).run().decision_records()[0]
    assert set(rec["metrics"]) == keys
    assert rec["metrics"]["e_total"] == 0.0 and rec["pool"] == {}


def test_scenario_workload_order_normalized():
    a = Scenario(name="w", workload=("network", "disk"))
    b = Scenario(name="w", workload=("disk", "network"))
    assert a == b == Scenario.from_dict(a.to_dict())


def test_integer_schedule_times_replay_byte_identical():
    """Scenario numerics are normalized at construction, so int-typed
    demand/shock times can't break the byte-identity contract."""
    sc = storm_scenario(duration_hours=12, interrupt_model="none",
                        inject_if_idle=False,
                        demand_schedule=((9, 80),),
                        shocks=(Shock(time=6, kind="price", factor=2),))
    res = ClusterSim(sc).run()
    assert ClusterSim.replay(res.records).run().recorder.dumps() == \
        res.recorder.dumps()


def test_run_refuses_after_event_stream_use():
    """Mixing the probe/event-stream API with run() would desynchronize
    the recorded market-state sequence — refused loudly."""
    sc = storm_scenario(duration_hours=6.0)
    sim = ClusterSim(sc)
    sim.current_snapshot()
    with pytest.raises(RuntimeError, match="fresh ClusterSim"):
        sim.run()


def test_t0_shock_visible_to_initial_decision():
    """DESIGN.md §9: a shock is visible to the same instant's decision —
    including the initial provisioning at t=0."""
    base = storm_scenario(duration_hours=6.0, interrupt_model="none",
                          inject_if_idle=False)
    shocked = storm_scenario(duration_hours=6.0, interrupt_model="none",
                             inject_if_idle=False,
                             shocks=(Shock(time=0.0, kind="price",
                                           factor=10.0),))
    cost_base = dict(ClusterSim(base).run().decisions)["initial"] \
        .metrics["hourly_cost"]
    cost_shocked = dict(ClusterSim(shocked).run().decisions)["initial"] \
        .metrics["hourly_cost"]
    assert cost_shocked > cost_base * 2
    # the scripted replica path orders the t=0 shock identically
    r = run_replicas(shocked, [shocked.interrupt_seed])[0]
    assert r.decision_records() == \
        ClusterSim(shocked).run().decision_records()


def test_trace_header_versioned():
    sc = storm_scenario(duration_hours=0.0)
    res = ClusterSim(sc).run()
    header = res.records[0]
    assert header["type"] == "header" and header["version"] == 1
    assert Scenario.from_dict(header["scenario"]) == sc
    bad = [dict(header, version=99)] + res.records[1:]
    with pytest.raises(ValueError):
        ClusterSim.replay(bad)


# ------------------------------------------------------- interrupt models ----

def _snapshot_index(catalog):
    return {o.offering_id: o for o in catalog}


def test_price_crossing_fires_iff_spot_above_bid():
    catalog = generate_catalog(seed=2, max_offerings=20)
    model = PriceCrossingInterruptModel(bid_factor=1.5)
    model.reset(catalog, seed=0)
    over = dataclasses.replace(catalog[0],
                               spot_price=catalog[0].spot_price * 1.6)
    under = dataclasses.replace(catalog[1],
                                spot_price=catalog[1].spot_price * 1.4)
    index = {over.offering_id: over, under.offering_id: under}
    pool = {over.offering_id: 4, under.offering_id: 3}
    notices = model.sample(index, pool, hours=1.0, now=5.0)
    assert [(n.offering_id, n.count, n.reason) for n in notices] == \
        [(over.offering_id, 4, "price-crossing")]


def test_price_crossing_at_bid_does_not_fire():
    catalog = generate_catalog(seed=2, max_offerings=5)
    model = PriceCrossingInterruptModel(bid_factor=1.0)
    model.reset(catalog, seed=0)
    # spot exactly at bid: strictly-greater semantics, no interrupt
    notices = model.sample(_snapshot_index(catalog),
                           {catalog[0].offering_id: 2}, 1.0, 0.0)
    assert notices == []


def test_rebalance_model_stamps_lead_time():
    catalog = generate_catalog(seed=2, max_offerings=10)
    inner = PriceCrossingInterruptModel(bid_factor=0.0)  # always fires
    model = RebalanceRecommendationModel(inner, lead_hours=2.5)
    model.reset(catalog, seed=0)
    notices = model.sample(_snapshot_index(catalog),
                           {catalog[0].offering_id: 3}, 1.0, now=4.0)
    assert len(notices) == 1
    n = notices[0]
    assert n.lead_hours == 2.5 and n.effective_time == 6.5
    assert n.reason.startswith("rebalance-recommendation")


def test_rebalance_lead_time_honored_by_engine():
    """A warning issued at tick t reclaims capacity only at t + lead."""
    sc = storm_scenario(
        interrupt_model="rebalance:6:price_crossing:0.0",  # fire every tick
        inject_if_idle=False, duration_hours=18.0)
    res = ClusterSim(sc).run()
    first = res.rounds[0]
    assert first.notices and not first.effective   # advisory only at t=6
    assert first.lost_nodes == 0
    second = res.rounds[1]                          # matured at t=12
    assert second.effective and second.lost_nodes > 0
    # every reclaimed notice waited out its full lead time
    for rd in res.rounds:
        for n in rd.effective:
            assert n.effective_time <= rd.time + 1e-9
            assert rd.time - n.time >= n.lead_hours - 1e-9


def test_make_interrupt_model_specs():
    assert make_interrupt_model("none").sample({}, {}, 1.0, 0.0) == []
    assert make_interrupt_model("price_crossing:2.5").bid_factor == 2.5
    m = make_interrupt_model("rebalance:4:price_crossing:1.1")
    assert m.lead_hours == 4.0 and m.inner.bid_factor == 1.1
    with pytest.raises(ValueError):
        make_interrupt_model("martian")


def test_pressure_model_matches_simulator_law(small_catalog):
    """Same probability law as the market's built-in sampler: under heavy
    pressure the dedicated-stream model also loses nodes."""
    model = make_interrupt_model("pressure")
    model.reset(small_catalog, seed=3)
    index = _snapshot_index(small_catalog)
    o = max(small_catalog, key=lambda o: o.t3)
    lost = sum(sum(n.count for n in model.sample(index,
                                                 {o.offering_id: o.t3 * 4},
                                                 4.0, 0.0))
               for _ in range(20))
    assert lost > 0


# ----------------------------------------------------------- engine shape ----

def test_demand_scale_up_merges_shortfall():
    sc = storm_scenario(interrupt_model="none", inject_if_idle=False,
                        pods=30, demand_schedule=((15.0, 90),))
    res = ClusterSim(sc).run()
    reasons = [r for r, _ in res.decisions]
    assert reasons[0] == "initial" and "demand" in reasons
    initial = dict(res.decisions)["initial"]
    demand_decision = dict(res.decisions)["demand"]
    # only the shortfall is provisioned; running capacity is kept, not
    # discarded — the merged pool covers the new demand
    assert demand_decision.pool.total_pods < 90
    assert (initial.pool.total_pods + demand_decision.pool.total_pods) >= 90
    assert res.pool.total_pods >= 90


def test_demand_scale_down_keeps_pool():
    sc = storm_scenario(interrupt_model="none", inject_if_idle=False,
                        pods=90, demand_schedule=((15.0, 20),))
    res = ClusterSim(sc).run()
    assert [r for r, _ in res.decisions] == ["initial"]   # no new decision
    initial = dict(res.decisions)["initial"]
    assert res.pool.as_dict() == initial.pool.as_dict()


def test_injection_skipped_when_advisory_matures():
    """Fault injection only fires on genuinely calm rounds: a maturing
    rebalance recommendation counts as this round's interrupt."""
    sc = storm_scenario(
        interrupt_model="rebalance:6:price_crossing:0.0",  # fire every tick
        inject_if_idle=True, duration_hours=18.0)
    res = ClusterSim(sc).run()
    matured_rounds = [rd for rd in res.rounds if rd.effective]
    assert matured_rounds
    for rd in matured_rounds:
        assert all(n.reason != "fault-injection" for n in rd.notices)


def test_lost_pods_use_per_item_capacity():
    """The Fig. 12 bugfix: losses count each item's actual Pod_i."""
    sc = storm_scenario()
    res = ClusterSim(sc).run()
    rounds = [rd for rd in res.rounds if rd.effective]
    assert rounds
    for rd in rounds:
        assert rd.lost_pods >= rd.lost_nodes   # every node hosts ≥ 1 pod
    # at least one loss involves a node hosting != 2 pods (the old hardcode)
    req = Request(pods=sc.pods, cpu_per_pod=sc.cpu_per_pod,
                  mem_per_pod=sc.mem_per_pod)
    assert any(rd.lost_pods != 2 * rd.lost_nodes for rd in rounds), \
        "catalog draw only produced 2-pod nodes; weaken ONLY if seeds change"


def test_kubepacs_policy_excludes_interrupted_offerings():
    sc = storm_scenario()
    res = ClusterSim(sc).run()
    for rd in res.rounds:
        if rd.decision is None or not rd.decision.pool.total_nodes:
            continue
        interrupted = {n.offering_id for n in rd.effective}
        chosen = {it.offering.offering_id for it in rd.decision.pool.items}
        assert not (interrupted & chosen)


def test_partial_final_tick_covers_horizon():
    """A duration that isn't a step multiple ends with a partial tick so
    the whole horizon is simulated and billed."""
    sc = storm_scenario(duration_hours=10.0, interrupt_model="none",
                        inject_if_idle=False)
    res = ClusterSim(sc).run()
    assert [rd.time for rd in res.rounds] == [6.0, 10.0]
    assert res.records[-1]["time"] == 10.0           # summary at horizon
    pool_rate = dict(res.decisions)["initial"].pool.hourly_cost
    assert res.total_cost == pytest.approx(10.0 * pool_rate)
    assert ClusterSim.replay(res.records).run().recorder.dumps() == \
        res.recorder.dumps()


def test_events_beyond_horizon_are_dropped():
    sc = storm_scenario(duration_hours=12.0, interrupt_model="none",
                        inject_if_idle=False,
                        demand_schedule=((20.0, 500),),
                        shocks=(Shock(time=30.0, kind="price", factor=9.0),))
    res = ClusterSim(sc).run()
    assert [r for r, _ in res.decisions] == ["initial"]
    assert res.records[-1]["time"] == 12.0
    assert not any(r["type"] in ("demand", "shock") for r in res.records)


def test_infeasible_replacement_decision_is_recorded():
    """An interrupt re-optimization that finds no feasible replacement
    still appears in the trace, like initial/demand decisions."""
    sc = storm_scenario(pods=40, duration_hours=18.0,
                        interrupt_model="none", inject_if_idle=True,
                        demand_schedule=((7.0, 10**7),))   # impossible demand
    res = ClusterSim(sc).run()
    recs = res.decision_records()
    demand_t = next(r["time"] for r in recs if r["reason"] == "demand")
    # the demand-change attempt and every re-optimization attempt after it
    # are infeasible — and every one of them is in the trace
    assert next(r for r in recs if r["reason"] == "demand")["pool"] == {}
    late_interrupts = [r for r in recs
                       if r["reason"] == "interrupt" and r["time"] > demand_t]
    assert late_interrupts, "injection should force a post-demand interrupt"
    assert all(r["pool"] == {} and r["metrics"]["e_total"] == 0.0
               for r in late_interrupts)
    # survivors were kept despite the infeasible replacement attempts
    assert ClusterSim.replay(res.records).run().recorder.dumps() == \
        res.recorder.dumps()


# ----------------------------------------------------- multi-seed runner ----

def test_run_replicas_matches_standalone_run():
    sc = storm_scenario()
    single = ClusterSim(sc).run()
    replicas = run_replicas(sc, [1, 2, 3])
    assert replicas[0].decision_records() == single.decision_records()
    assert replicas[0].total_cost == single.total_cost
    # different interruption seeds genuinely diverge
    assert any(r.decision_records() != single.decision_records()
               for r in replicas[1:])


def test_run_replicas_rejects_fulfillment_scenarios():
    """Live fulfillment consumes the market price RNG; a scripted shared
    path cannot reproduce it, so the combination is an explicit error."""
    sc = storm_scenario(apply_fulfillment=True)
    with pytest.raises(ValueError, match="apply_fulfillment"):
        run_replicas(sc, [0, 1])


def test_run_replicas_shares_compiled_market():
    sc = storm_scenario(interrupt_model="none", inject_if_idle=False,
                        duration_hours=12.0)
    replicas = run_replicas(sc, [0, 1, 2, 3])
    assert len(replicas) == 4
    # no interrupts -> identical decisions across replicas (pure sharing)
    first = replicas[0].decision_records()
    for r in replicas[1:]:
        assert r.decision_records() == first


# --------------------------------------- scenario-derived fig benchmarks ----

def test_fig9_via_engine_matches_direct_simulator():
    """The engine's fulfillment probes reproduce the pre-refactor driver,
    which called SpotMarketSimulator.fulfill directly."""
    from benchmarks import fig9_t3_fulfillment
    from repro.core import SpotMarketSimulator

    cat = generate_catalog(seed=0, max_offerings=400)
    out = fig9_t3_fulfillment.run(cat)
    assert out["monotone"]
    assert out["trace_records"] > 1

    sim = SpotMarketSimulator(cat, seed=0)
    snap = sim.snapshot()
    lo, hi = 0, 5
    offers = [o for o in snap if lo <= o.t3 < hi][:40]
    expect = float(np.mean([sim.fulfill(o.offering_id, 50)
                            for o in offers]))
    assert out["rows"][0]["mean_fulfilled"] == expect


def test_figure_sweep_registry_resolves():
    """Every ``benchmarks.run.ALL`` entry names a driver module that exists
    on disk under that name and has a callable ``main`` (no driver runs)."""
    import os

    from benchmarks import run

    names = [name for name, _ in run.ALL]
    assert len(set(names)) == len(names)
    pkg_dir = os.path.dirname(run.__file__)
    for name, mod in run.ALL:
        assert mod.__name__ == f"benchmarks.{name}"
        assert os.path.isfile(os.path.join(pkg_dir, f"{name}.py"))
        assert callable(getattr(mod, "main", None))


def test_fig12_via_engine(small_catalog):
    from benchmarks import fig12_interrupts
    out = fig12_interrupts.run(small_catalog, rounds=3)
    assert out["recovery_s_ours"] < out["recovery_s_karpenter"]
    assert out["interrupted_nodes"] > 0
    assert np.isfinite(out["node_price_ours"])
