"""The premises of ``karpenter_zone_m_250m.tick4_24k``: its market takes
the LP prune's size table at gcd 8, every demand the mix can draw lies on
the gcd rung of demand coarsening (DESIGN.md §14) in one program shape,
the device plane accepts its batches, its row-counter readers read the
program's counters and nothing without them, and one tick of the mix
served through the harness's tick kind equals the plain reference."""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import cells, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
CELL = "karpenter_zone_m_250m.tick4_24k"
CONFIG, MIX = (run.load_json(os.path.join(BENCH, sub, name + ".json"))
               for sub, name in (("configs", "karpenter_zone_m_250m"),
                                 ("traffic", "tick4_24k")))


def _market():
    from repro.core import Offering, Request, compile_market
    from repro.core.provisioner import preprocess

    offs = cells._offerings(CONFIG, MIX, 0)
    items = preprocess([Offering(**o) for o in offs],
                       Request(1, CONFIG["pod_cpu"], CONFIG["pod_mem_gib"]))
    return items, compile_market(items)


def test_market_is_gcd_8_with_the_size_table():
    _items, market = _market()
    assert market.n == 272 and market.pods_gcd == 8
    assert len(np.unique(market.b_pods)) == 41


def test_every_demand_lies_on_the_gcd_rung():
    from repro.core import DEFAULT_COARSENING as cfg

    r = traffic.demand_range(MIX)
    assert (r.start, r.stop - 1) == (20_400, 27_600)
    assert cfg.threshold < r.start
    assert r.stop - 1 <= cfg.max_rows * 8


def test_every_tick_compiles_one_shape():
    """The shape key depends on a tick's largest demand: every largest
    demand of the warm-up and the window gives RC 8,193 (the threshold
    bucketed, plus one) and D 4."""
    from repro.core.backend import FusedJaxBackend as F

    _items, market = _market()
    top = traffic.strata(MIX)[-1]
    for largest in {*top, *traffic.largest_demands(MIX)}:
        assert F._shape_key(F, market, [largest, traffic.demand_range(
            MIX).start], MIX["decisions_per_tick"]) == (512, 1152, 8193, 4)


def _run(counters):
    return run.RunData(kind="tick", requests=3, decisions=12,
                       latencies_s=[0.1] * 3, window_s=0.3, setup_s=1.0,
                       calls_wall={}, counters=counters, trace=None)


@pytest.mark.parametrize("name,share", [("gcd_row_share", 75.0),
                                        ("dp_fill_share", 40.0)])
def test_row_counter_readers(name, share):
    read = run.load_reader(name)
    assert read(_run({"hits": 5, "misses": 0})) is None
    assert read(_run({"dp_rows": 0, "gcd_rows": 0, "dp_cols_needed": 0,
                      "dp_cols_computed": 0})) is None
    assert read(_run({"dp_rows": 40, "gcd_rows": 30, "dp_cols_needed": 400,
                      "dp_cols_computed": 1000})) == share


@pytest.fixture(scope="module")
def backend():
    from repro.core import make_backend
    return make_backend("jax:fused")


def test_device_plane_accepts_the_extremes(backend):
    from repro.core import exact

    items, market = _market()
    r = traffic.demand_range(MIX)
    rec = backend.fused_gss_record(items, market, [r.stop - 1, r.start],
                                   [None, None], exact.alpha_grid(9), 0.01)
    assert rec is not None
    info = backend.device_cache_info()
    assert info["declined_batches"] == info["host_dp_groups"] == 0
    assert 0 < info["gcd_rows"] <= info["dp_rows"]


def test_one_tick_of_the_mix_equals_the_reference(backend):
    """A tick of two NodePools of the mix (one with its exclusion) through
    ``TickCell`` on ``jax:fused``: every decision and probe equals the
    reference, every DP row of the golden searches runs on the device, and
    most rows take the gcd rung."""
    mix = dict(MIX, decisions_per_tick=2, excluded_share_of_decisions=0.5)
    unit = cells.TickCell(CONFIG, mix, 0, backend)
    rng = np.random.default_rng(2 ** 33 + 9)
    before = backend.device_cache_info()
    t = unit.next_request(rng)
    assert sum(bool(e) for e in t.excluded) == 1
    window = [(t, unit.serve(t))]
    info = {k: v - before[k] for k, v in backend.device_cache_info().items()}
    cmp = cells.compare(unit.checks(window, rng), CONFIG, mix)
    assert cmp.decisions == 2 and cmp.probes >= 2 * mix["prescan"]
    assert cmp.decisions_mismatched == cmp.probes_mismatched == 0
    assert info["declined_batches"] == info["host_dp_groups"] == 0
    assert info["fallback_solves"] == 0 and info["fused_records"] == 1
    assert info["gcd_rows"] >= 0.9 * info["dp_rows"] > 0
