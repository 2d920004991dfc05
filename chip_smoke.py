#!/usr/bin/env python3
"""Chip smoke test: drive KubePACS's device decision plane on one TPU.

Runs the provisioning decision — bracketed golden-section search over α
around the bounded-knapsack cover DP — through its user entry points
(``bracketed_gss_many`` and ``run_fleet`` with ``backend="jax:fused"``) at
real sizes, and checks every selection against the NumPy engine:

  probe   the scalar ops the row solver uses, device vs NumPy, bitwise
          (int64 must match exactly; float64 mismatches are reported —
          the chip emulates float64 with pairs of float32)
  a       fleet tick: 100 offerings x 1,000 pods x 32 jittered decisions
  b       acceptance market: 250 offerings x 5,000 pods x 32 decisions
  c       region catalog: 4,000 offerings x 1,000 pods x 8 decisions
  d       run_fleet of the interrupt-storm scenario (250 offerings) over
          32 seeds, traces byte-identical to the NumPy run

Each phase prints one JSON line: padded shapes, compile seconds, a smoke
timing per decision (not a metric), the plane's counters and whether the
selections equal NumPy's.  The last line is ``{"ok": true, "device":
{...}}``.  Without a TPU it exits non-zero and prints no result.

Usage:  python chip_smoke.py
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
JITTER = 0.15
TOLERANCE = 0.01
STEADY_RUNS = 2


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


def _jittered(base: int, n: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [int(base * (1 + JITTER * (2 * rng.random() - 1)))
            for _ in range(n)]


def _summary(results):
    return [((None if p is None else p.as_dict()),
             (None if p is None else p.alpha), t.alphas, t.e_totals)
            for p, t in results]


def op_probe():
    """Bitwise device-vs-NumPy check of the row solver's ops."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import exact

    rng = np.random.default_rng(0)
    n = 4096
    x, y, a = (rng.uniform(0.5, 4.0, n), rng.uniform(0.5, 4.0, n),
               rng.uniform(0.0, 1.0, n))
    phi = (math.sqrt(5.0) - 1.0) / 2.0

    def seq(v):
        return jax.lax.scan(lambda c, e: (c + e, c + e),
                            jnp.zeros((), v.dtype), v)[1]

    f64 = {"product": (lambda x, y, a: x * y, x * y),
           "sum": (lambda x, y, a: x + y, x + y),
           "division": (lambda x, y, a: x / y, x / y),
           "compare": (lambda x, y, a: x * y < y + a, x * y < y + a),
           "seq_cumsum": (lambda x, y, a: seq(x), np.cumsum(x)),
           "golden": (lambda x, y, a: a + phi * (y - a), a + phi * (y - a))}
    f64_mismatch = {k: int(np.sum(np.asarray(jax.jit(f)(x, y, a)) != ref))
                    for k, (f, ref) in f64.items()}

    K = rng.integers(0, exact.ALPHA_ONE + 1, n)
    W = rng.integers(0, 1 << 42, n)
    Q = rng.integers(0, 1 << 40, n)
    P = rng.integers(1, 1 << 12, n)
    C = np.abs(exact.coefficients(K, W, Q))
    i64 = {"coefficients": (lambda K, W, Q, P: exact.coefficients(K, W, Q),
                            exact.coefficients(K, W, Q)),
           "product": (lambda K, W, Q, P: W * P, W * P),
           "sum": (lambda K, W, Q, P: W + Q, W + Q),
           "division": (lambda K, W, Q, P: jnp.abs(
               exact.coefficients(K, W, Q)) // P, C // P),
           "compare": (lambda K, W, Q, P: W < Q * P, W < Q * P),
           "cumsum": (lambda K, W, Q, P: jax.lax.associative_scan(
               jnp.add, Q), np.cumsum(Q)),
           "golden": (lambda K, W, Q, P: exact.golden_width(K),
                      exact.golden_width(K)),
           "stable_argsort": (lambda K, W, Q, P: jnp.argsort(
               P // 64, stable=True), np.argsort(P // 64, kind="stable"))}
    i64_mismatch = {k: int(np.sum(np.asarray(jax.jit(f)(K, W, Q, P)) != ref))
                    for k, (f, ref) in i64.items()}
    return {"phase": "probe", "int64_mismatches": i64_mismatch,
            "float64_mismatches": f64_mismatch,
            "ok": not any(i64_mismatch.values())}


def _counters(be):
    info = be.device_cache_info()
    return {k: info[k] for k in ("fused_records", "fallback_solves",
                                 "verify_solves", "declined_batches",
                                 "host_dp_groups")}


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


def gss_phase(name, be, numpy_be, n_offerings, n_items, pods, n_dec):
    """One bracketed_gss_many batch at real size: NumPy reference, first
    fused call (compile + run), then STEADY_RUNS timed fused calls."""
    from repro.core import Request, compile_market, generate_catalog, \
        preprocess
    from repro.core.gss import bracketed_gss_many

    cat = generate_catalog(seed=0, max_offerings=n_offerings)
    items = preprocess(cat, Request(pods=pods, cpu_per_pod=2,
                                    mem_per_pod=2))
    items = items[:n_items] if n_items else items
    market = compile_market(items)
    demands = _jittered(pods, n_dec)
    fake = lambda: 0.0                                     # noqa: E731

    def run(backend):
        return bracketed_gss_many(items, demands, tolerance=TOLERANCE,
                                  market=market, timer=fake, backend=backend)

    ref = _summary(run(numpy_be))
    before = _counters(be)
    t0 = time.perf_counter()
    first = run(be)
    first_s = time.perf_counter() - t0
    equal = _summary(first) == ref
    walls = []
    for _ in range(STEADY_RUNS):
        t0 = time.perf_counter()
        equal &= _summary(run(be)) == ref
        walls.append(time.perf_counter() - t0)
    steady = min(walls)
    N, B, RC, D = be._shape_key(market, demands, len(demands))
    counters = _delta(_counters(be), before)
    calls = 1 + STEADY_RUNS
    ok = (equal and counters["fallback_solves"] == 0
          and counters["fused_records"] == calls
          and counters["declined_batches"] == 0
          and counters["host_dp_groups"] == 0)
    return {"phase": name, "offerings": market.n, "bundles": market.n_bundles,
            "decisions": n_dec, "base_pods": pods,
            "shapes": {"N": N, "B": B, "RC": RC, "D": D},
            "compile_s": round(max(first_s - steady, 0.0), 3),
            "smoke_timing_ms_per_decision": round(steady / n_dec * 1e3, 3),
            **counters, "batches": calls, "selections_equal_numpy": equal,
            "ok": ok}


def fleet_phase(be, seeds=32):
    """run_fleet of risk.backtest's interrupt-storm scenario, NumPy vs the
    device plane: traces must be byte-identical."""
    from repro.risk import backtest
    from repro.sim import run_fleet

    sc = backtest.interrupt_storm_scenario(max_offerings=250)
    seeds = list(range(seeds))
    base = run_fleet(sc, seeds, record_traces=True)
    before = _counters(be)
    builds, programs = be.program_builds, set(be._fused_cache)
    t0 = time.perf_counter()
    fused = run_fleet(sc, seeds, record_traces=True, backend=be)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = run_fleet(sc, seeds, record_traces=True, backend=be)
    steady = time.perf_counter() - t0
    equal = all(a.records == b.records == c.records and
                a.total_cost == b.total_cost == c.total_cost
                for a, b, c in zip(base, fused, again))
    counters = _delta(_counters(be), before)
    decisions = sum(len(r.decisions) for r in base)
    ok = (equal and counters["fallback_solves"] == 0
          and counters["fused_records"] > 0
          and counters["declined_batches"] == 0
          and counters["host_dp_groups"] == 0)
    shapes = sorted({k[1:5] for k in set(be._fused_cache) - programs})
    return {"phase": "d_fleet_storm", "seeds": len(seeds),
            "decisions": decisions,
            "shapes": [dict(zip("N B RC D".split(), s)) for s in shapes],
            "programs_compiled": be.program_builds - builds,
            "compile_s": round(max(first_s - steady, 0.0), 3),
            "smoke_timing_ms_per_decision": round(
                steady / max(decisions, 1) * 1e3, 3),
            **counters, "selections_equal_numpy": equal, "ok": ok}


def main() -> int:
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        return _fail(f"no KubePACS checkout beside {__file__}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX found {dev.platform!r} "
                     f"({dev.device_kind}); the smoke runs only on the chip")
    from repro.core import NumpyBackend, make_backend

    be = make_backend("jax:fused")
    numpy_be = NumpyBackend()
    phases = [
        ("probe", op_probe),
        ("a_fleet_tick", lambda: gss_phase("a_fleet_tick", be, numpy_be,
                                           2000, 100, 1000, 32)),
        ("b_acceptance_market", lambda: gss_phase(
            "b_acceptance_market", be, numpy_be, 2000, 250, 5000, 32)),
        ("c_region_catalog", lambda: gss_phase(
            "c_region_catalog", be, numpy_be, 4000, None, 1000, 8)),
        ("d_fleet_storm", lambda: fleet_phase(be))]
    ok = True
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            rec = phase()
        except Exception as exc:  # report the phase, keep the verdict
            rec = {"phase": name, "error": repr(exc), "ok": False}
        rec["phase_wall_s"] = round(time.perf_counter() - t0, 3)
        print(json.dumps(rec), flush=True)
        ok &= rec["ok"]
    if not ok:
        return _fail("a phase failed (see the phase lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
