"""CPU tests of the benchmark harness: discovery by name, traffic
determinism, warm-up coverage, the reference against the NumPy engine,
the control and the planted faults, and the refusal to measure off the
chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import catalog, cells, reference, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
SPEC = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")

#: a deployment small enough for a CPU run: one zone, m family, gens 5-6
TINY_CONFIG = dict(run.load_json(os.path.join(
    BENCH, "configs", "karpenter_zone_m.json")), name="tiny_zone",
    generations=[5, 6], offerings=128)
TINY_TICK = dict(run.load_json(os.path.join(BENCH, "traffic",
                                            "tick32.json")),
                 decisions_per_tick=4, pods_mean=60, check_decisions=8)


def mixes(kind):
    return sorted(n[:-5] for n in os.listdir(os.path.join(BENCH, "traffic"))
                  if run.load_json(os.path.join(BENCH, "traffic", n))
                  ["kind"] == kind)


def tick_mixes():
    return mixes("tick")


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_every_cell_resolves_by_name():
    """Each cell's configuration, mix and metrics are files named after
    them; each configuration file gives the offering count it states."""
    for cell in SPEC["workloads"]:
        config = run.load_json(os.path.join(BENCH, "configs",
                                            cell["config"] + ".json"))
        mix = run.load_json(os.path.join(BENCH, "traffic",
                                         cell["traffic"] + ".json"))
        assert mix["kind"] in cells.KINDS
        assert len(catalog.deployment_offerings(config, 3)) == \
            config["offerings"]
        for section in ("end_to_end", "per_layer"):
            for m in run.cell_metrics(SPEC, section, cell["name"]):
                assert callable(run.load_reader(m["name"]))
    for c in SPEC["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a mix and a metric added as new files plus new
    BENCHMARK.json entries run without an edit to any existing file."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    spec = json.loads(json.dumps(SPEC))
    _write(tmp_path / "bench" / "configs" / "tiny_zone.json", TINY_CONFIG)
    _write(tmp_path / "bench" / "traffic" / "tiny_tick.json", TINY_TICK)
    (tmp_path / "bench" / "metrics" / "ticks_per_s.py").write_text(
        "def read(run):\n    return run.requests / run.window_s\n")
    spec["configs"].append({"name": "tiny_zone", "source": "test",
                            "file": "bench/configs/tiny_zone.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_zone.tiny_tick",
                              "config": "tiny_zone", "traffic": "tiny_tick",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "ticks_per_s", "unit": "ticks/s",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny_zone.tiny_tick"]})
    _write(tmp_path / "BENCHMARK.json", spec)
    code = ("import json, sys; sys.path.insert(0, '.'); from bench import "
            "run; print(json.dumps(run.measure('tiny_zone.tiny_tick', 5, "
            "0.5, False, require_tpu=False)))")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=CPU_ENV, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"]
    # tick_p95_ms lists its cells, and this one is not among them
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s",
                                      "ticks_per_s"}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_seed_solves_the_same_market(cell):
    """The market decides how much work a decision is (probes per search,
    bundles left to the cover DP), so a cell's catalog is the same for
    every seed and only the requests change with it."""
    config = run.load_json(os.path.join(BENCH, "configs",
                                        cell["config"] + ".json"))
    mix = run.load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    assert cells._offerings(config, mix, 7) == \
        cells._offerings(config, mix, 2 ** 33 + 5)


@pytest.mark.parametrize("name", tick_mixes())
def test_tick_traffic_is_a_function_of_the_seed(name):
    mix = run.load_json(os.path.join(BENCH, "traffic", name + ".json"))
    ids = [f"o{i}" for i in range(300)]

    def draw(seed):
        rng = np.random.default_rng(seed)
        return [traffic.tick(mix, ids, rng) for _ in range(20)]

    a, b, c = draw(2 ** 33 + 1), draw(2 ** 33 + 1), draw(7)
    assert a == b
    assert a != c
    r = traffic.demand_range(mix)
    n_ex = round(mix["decisions_per_tick"]
                 * mix["excluded_share_of_decisions"])
    for t in a:
        assert len(set(t.demands)) == len(t.demands) == \
            mix["decisions_per_tick"]
        assert all(d in r for d in t.demands)
        assert sum(bool(e) for e in t.excluded) == (
            n_ex if mix["excluded_share_of_offerings"] else 0)


@pytest.mark.parametrize("name", tick_mixes())
def test_warmup_reaches_every_largest_demand_bucket(name):
    """Shape buckets depend on a tick's largest demand (the decision count
    is fixed).  The warm-up's largest demands span every tick's largest
    demand in steps of at most 64 pods, narrower than any bucket, so each
    bucket a window tick can reach was warmed."""
    mix = run.load_json(os.path.join(BENCH, "traffic", name + ".json"))
    warm = traffic.largest_demands(mix)
    assert all(b - a <= 64 for a, b in zip(warm, warm[1:]))
    rng = np.random.default_rng(11)
    ids = [f"o{i}" for i in range(300)]
    for _ in range(2000):
        t = traffic.tick(mix, ids, rng)
        assert warm[0] <= max(t.demands) <= warm[-1]
        assert len(t.demands) == mix["decisions_per_tick"]
    for m in warm:
        assert max(traffic.with_largest(traffic.tick(mix, ids, rng),
                                        m).demands) == m


def _backtest_shapes(config, mix, market_seeds):
    """Shape keys (N, B, RC, D) of the device programs that a backtest mix
    reaches on the given market paths, by the program's own bucket rule:
    one replica stands for all, since under the mix's deterministic
    interrupts they coincide and the memo leaves one decision per batch."""
    from repro.core import (NumpyBackend, Request, compile_market,
                            preprocess)
    from repro.core.backend import FusedJaxBackend

    unit = cells.BacktestCell(config, mix, 0, NumpyBackend())
    request = Request(1, config["pod_cpu"], config["pod_mem_gib"])
    keys = set()
    for market_seed in market_seeds:
        [res] = unit.serve(traffic.Backtest(market_seed, [1]))
        snaps = [unit.catalog] + [rd.snapshot for rd in res.rounds
                                  if rd.decision is not None]
        for snap, (_, decision) in zip(snaps, res.decisions):
            market = compile_market(preprocess(snap, request))
            keys.add(FusedJaxBackend._shape_key(
                FusedJaxBackend, market, [decision.pool.request.pods], 1))
    return keys


@pytest.mark.parametrize("cell", [c for c in SPEC["workloads"]
                                  if c["traffic"] in mixes("backtest")],
                         ids=lambda c: c["name"])
def test_backtest_warmup_reaches_every_window_shape(cell):
    """A market path's capacity moves the bundle count, so each path can
    reach its own program shapes: the warm-up's paths, which the window
    never walks, reach every shape the window's paths do."""
    config = run.load_json(os.path.join(BENCH, "configs",
                                        cell["config"] + ".json"))
    mix = run.load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    assert not set(mix["warmup_market_seeds"]) & set(mix["market_seeds"])
    window = _backtest_shapes(config, mix, mix["market_seeds"])
    assert window <= _backtest_shapes(config, mix,
                                      mix["warmup_market_seeds"])


def test_backtest_traffic_walks_every_path_in_seeded_order():
    mix = run.load_json(os.path.join(BENCH, "traffic", "storm_fleet.json"))

    def draw(seed):
        rng = np.random.default_rng(seed)
        return [traffic.market_pass(mix, rng) for _ in range(3)]

    a, b, c = draw(2 ** 33 + 1), draw(2 ** 33 + 1), draw(7)
    assert a == b != c
    for one_pass in a + c:
        assert sorted(one_pass) == sorted(mix["market_seeds"])


def _engine_answers(offerings, demands, excluded):
    from repro.core import NumpyBackend, Offering, Request, compile_market
    from repro.core.gss import bracketed_gss_many
    from repro.core.provisioner import exclusion_mask, preprocess

    items = preprocess([Offering(**o) for o in offerings], Request(1, 2, 2))
    market = compile_market(items)
    res = bracketed_gss_many(items, demands, tolerance=0.01, market=market,
                             excludes=[exclusion_mask(items, e)
                                       for e in excluded],
                             backend=NumpyBackend())
    return [((p.as_dict() if p else {}), (p.alpha if p else None),
             list(zip(t.alphas, t.e_totals))) for p, t in res]


MARKETS = [(3, ["m"], [5, 6], ["us-east-1a"], 90),
           (4, ["c", "r"], [7], ["us-east-1b"], 40),
           (5, ["m", "c"], [5, 8], None, 300)]


@pytest.mark.parametrize("seed,families,gens,zones,pods", MARKETS)
def test_reference_equals_numpy_engine(seed, families, gens, zones, pods):
    offs = catalog.offerings(seed, ["us-east-1"], families, gens, zones)
    ids = [o["offering_id"] for o in offs]
    rng = np.random.default_rng(seed)
    demands = [int(d) for d in rng.integers(pods // 2, pods * 3 // 2, 4)]
    excluded = [set(rng.choice(ids, 3, replace=False)) if i % 2 else set()
                for i in range(4)]
    m = reference.Market(offs, 2, 2)
    ref = [reference.decide(m, d, e) for d, e in zip(demands, excluded)]
    assert ref == _engine_answers(offs, demands, excluded)
    assert all(a[0] for a in ref)


def test_control_fails_the_comparison():
    """The reference one precision step down (int32 costs) in the
    program's place: every decision and its probes differ."""
    offs = catalog.offerings(3, ["us-east-1"], ["m"], [5, 6], ["us-east-1a"])
    mix = {"tolerance": 0.01, "prescan": 9}
    config = {"pod_cpu": 2, "pod_mem_gib": 2}
    checks = [cells.Check(offs, "t", pods, frozenset(), None)
              for pods in (40, 75, 110)]
    exact = cells.compare(checks, config, mix,
                          cells.answers(checks, config, mix))
    control = cells.compare(checks, config, mix,
                            cells.answers(checks, config, mix, "int32"))
    assert exact.probes_mismatched == exact.decisions_mismatched == 0
    assert control.decisions == control.decisions_mismatched == 3
    assert control.probes_mismatched >= control.probes


@pytest.fixture(scope="module")
def fault_results(tmp_path_factory):
    d = tmp_path_factory.mktemp("faults")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "tiny_zone.tiny_tick",
                              "config": "tiny_zone", "traffic": "tiny_tick",
                              "chips": 1, "why": "test"})
    storm = run.load_json(os.path.join(BENCH, "traffic", "storm_fleet.json"))
    # paths whose shapes at this size the first warm-up path reaches
    storm.update(replicas=3, market_seeds=[3, 4, 5, 11],
                 warmup_market_seeds=[31])
    storm["scenario"] = dict(storm["scenario"], pods=40)
    spec["workloads"].append({"name": "tiny_zone.tiny_storm",
                              "config": "tiny_zone", "traffic": "tiny_storm",
                              "chips": 1, "why": "test"})
    for name, obj in (("spec", spec), ("config", TINY_CONFIG),
                      ("tick", TINY_TICK), ("storm", storm)):
        _write(d / f"{name}.json", obj)
    script = os.path.join(BENCH, "tests", "fault_runs.py")
    results = {}
    for workload, mix, faults in (
            ("tiny_zone.tiny_tick", "tick",
             ["none", "control", "answer_altered", "half_batch",
              "stale_state"]),
            ("tiny_zone.tiny_storm", "storm",
             ["none", "control", "answer_altered"])):
        out = subprocess.run(
            [sys.executable, script, str(d / "spec.json"),
             str(d / "config.json"), str(d / f"{mix}.json"), workload,
             *faults], cwd=ROOT, env=CPU_ENV, capture_output=True,
            text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        for line in out.stdout.strip().splitlines():
            rec = json.loads(line)
            results[(mix, rec["fault"])] = rec
    return results


@pytest.mark.parametrize("mix,fault", [
    ("tick", "control"), ("tick", "answer_altered"), ("tick", "half_batch"),
    ("tick", "stale_state"), ("storm", "control"),
    ("storm", "answer_altered")])
def test_planted_fault_is_not_correct(fault_results, mix, fault):
    assert fault_results[(mix, "none")]["correct"]
    rec = fault_results[(mix, fault)]
    assert not rec["correct"]
    assert (rec["compared"]["decisions_mismatched"]["value"]
            + rec["compared"]["probes_mismatched"]["value"]) > 0


def test_refuses_to_measure_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "karpenter_zone_m.tick32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=CPU_ENV, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "karpenter_zone_m.tick32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=CPU_ENV, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
