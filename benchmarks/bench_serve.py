"""Serving co-simulation benchmark: served QPS per dollar under SLO
(DESIGN.md §15).

Emits ``BENCH_serve.json`` — the fig-style policy comparison for the
serving scenario family:

  * per workload (``diurnal`` headline; ``bursty`` / ``flash`` in the
    full run) every policy provisions the *same* square-root-staffed pod
    demand, and the report integrates served / SLO-served QPS-hours
    against each policy's capacity timeline (recovery warm-up charged);
  * ``headline.serve_qps_per_dollar_ratio`` — serving_slo over
    karpenter_like on SLO-served QPS-hours per dollar, diurnal — must
    meet ``TARGET_SLO_QPS_RATIO`` at equal-or-better SLO attainment;
  * before timing anything the bench re-proves the determinism contract
    (same seed ⇒ identical workload trace digest AND an identical serving
    report on a re-run) and asserts **zero SLO-mask infeasibilities** for
    serving_slo on the pinned market — a comparison against an infeasible
    or non-reproducible run would be meaningless, so these raise.

The comparison runs in the ambient perf-model mode, which is how the jax
leg exercises the roofline table and the no-jax leg the analytic fallback
end to end.

Usage:
  python -m benchmarks.bench_serve [--smoke] [--json PATH]

``make bench-serve`` refreshes the checked-in BENCH_serve.json.
"""

from __future__ import annotations

import argparse
import json
import platform
import time
from typing import List, Optional

import numpy as np

from repro.serve_sim import (WorkloadSpec, build_serve_scenario,
                             run_serving, trace_digest)

#: acceptance bar (ISSUE 8): serving_slo ≥ 1.2× SLO-served QPS-hours per
#: dollar over karpenter_like on the diurnal scenario, at equal-or-better
#: SLO attainment
TARGET_SLO_QPS_RATIO = 1.2

POLICIES = ("serving_slo", "karpenter_like", "kubepacs",
            "fixed_alpha:0.5", "kubepacs_risk")

#: ratio denominators are floored so one pathological karpenter run (zero
#: SLO-served traffic) reports a huge finite ratio instead of inf/NaN
_DENOM_FLOOR = 1e-9


def _run_policy(kind: str, policy: str, duration_hours: float) -> tuple:
    ss = build_serve_scenario(kind, policy=policy,
                              duration_hours=duration_hours)
    t0 = time.perf_counter()
    report = run_serving(ss, clock=lambda: 0.0)
    return report, time.perf_counter() - t0


def _determinism_check(duration_hours: float) -> bool:
    """Same seed ⇒ byte-identical trace digest; same scenario ⇒ identical
    serving report (policies are replay-RNG-free, the table is digest-
    cached, and the integration is exact)."""
    spec = WorkloadSpec(kind="diurnal", seed=123)
    if trace_digest(spec) != trace_digest(WorkloadSpec(kind="diurnal",
                                                       seed=123)):
        return False
    a, _ = _run_policy("diurnal", "serving_slo", duration_hours)
    b, _ = _run_policy("diurnal", "serving_slo", duration_hours)
    return a.as_dict() == b.as_dict()


def _compare(kind: str, policies, duration_hours: float) -> dict:
    rows = {}
    for policy in policies:
        report, wall = _run_policy(kind, policy, duration_hours)
        d = report.as_dict()
        d["wall_s"] = round(wall, 3)
        rows[policy] = d
    return rows


def run(smoke: bool = False, json_path: Optional[str] = None) -> dict:
    duration = 12.0 if smoke else 24.0
    kinds = ("diurnal",) if smoke else ("diurnal", "bursty", "flash")

    if not _determinism_check(duration):
        raise AssertionError(
            "serving co-sim is not deterministic: same seed produced a "
            "different trace digest or serving report — refusing to "
            "benchmark a non-reproducible run")

    comparisons = {kind: _compare(kind, POLICIES, duration)
                   for kind in kinds}

    slo = comparisons["diurnal"]["serving_slo"]
    karp = comparisons["diurnal"]["karpenter_like"]
    if slo["infeasible_decisions"]:
        raise AssertionError(
            f"serving_slo hit {slo['infeasible_decisions']} SLO-mask "
            "infeasibilities on the pinned market — the mask is "
            "over-constraining the ILP (acceptance: zero)")
    ratio = slo["slo_qps_hours_per_dollar"] / max(
        karp["slo_qps_hours_per_dollar"], _DENOM_FLOOR)
    attainment_ok = slo["slo_attainment"] >= karp["slo_attainment"] - 1e-9

    out = {
        "benchmark": "bench_serve",
        "python": platform.python_version(),
        "numpy": np.__version__,
        "machine": platform.machine(),
        "perf_mode": slo["perf_mode"],
        "duration_hours": duration,
        "slo_ms": slo["slo_ms"],
        "workload_digest": slo["workload_digest"],
        "determinism_checked": True,
        "target_slo_qps_ratio": TARGET_SLO_QPS_RATIO,
        "comparisons": comparisons,
        "headline": {
            "serve_qps_per_dollar_ratio": round(ratio, 3),
            "serving_slo_attainment": round(slo["slo_attainment"], 4),
            "karpenter_attainment": round(karp["slo_attainment"], 4),
            "attainment_ok": attainment_ok,
            "serving_slo_qps_per_dollar":
                round(slo["slo_qps_hours_per_dollar"], 2),
            "karpenter_slo_qps_per_dollar":
                round(karp["slo_qps_hours_per_dollar"], 2),
            "infeasible_decisions": slo["infeasible_decisions"],
            "meets_target": (ratio >= TARGET_SLO_QPS_RATIO
                             and attainment_ok),
        },
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
    return out


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="diurnal only, 12 h horizon (CI)")
    ap.add_argument("--json", default="",
                    help="output record path (e.g. BENCH_serve.json; "
                         "default: don't write)")
    args = ap.parse_args(argv if argv is not None else [])
    out = run(smoke=args.smoke, json_path=args.json or None)
    h = out["headline"]
    detail = (f"mode={out['perf_mode']}"
              f";slo_qps_ratio={h['serve_qps_per_dollar_ratio']}x"
              f";att={h['serving_slo_attainment']}"
              f"vs{h['karpenter_attainment']}"
              f";infeasible={h['infeasible_decisions']}"
              f";target>={out['target_slo_qps_ratio']}x:"
              f"{'met' if h['meets_target'] else 'MISSED'}")
    wall = out["comparisons"]["diurnal"]["serving_slo"]["wall_s"]
    print(f"bench_serve,{round(wall * 1e6)},{detail}")
    return out


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
