#!/usr/bin/env python3
"""Run sets of benchmark runs, one new process each, one after another, and
print the spread of every end-to-end metric.

    python3 bench/sets.py --workload <cell> --seeds 11,12,13 [--seconds 20]
        [--trace 0|1] [--sets 2] [--out chiprun_out/sets.jsonl] [--control]

Each set runs ``bench/run.py`` once per seed, in order; ``--sets 2`` runs
the same seeds again as a second set.  This process never imports JAX, so
each child has the chip to itself.  Every run's result line (with its
seed, set and exit code) is appended to ``--out``; the last line printed
is a summary: per metric, each set's median and spread (first to third
quartile over the median, by ``statistics.quantiles(values, n=4)``), also
with each set's run farthest from its median left out.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def spread(values: List[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def trimmed(values: List[float]) -> List[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def one_run(args, seed: int, set_no: int) -> Dict:
    cmd = [sys.executable, os.path.join(BENCH, "run.py"),
           "--workload", args.workload, "--seed", str(seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace)]
    if args.control:
        cmd.append("--control")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                              timeout=args.timeout)
    except subprocess.TimeoutExpired as exc:
        # subprocess.run has killed and reaped the child
        proc = subprocess.CompletedProcess(
            cmd, 124, exc.stdout or "", f"timed out: {exc}")
        if isinstance(proc.stdout, bytes):
            proc.stdout = proc.stdout.decode(errors="replace")
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": args.workload, "seed": seed, "set": set_no,
            "rc": proc.returncode, "wall_s": wall, "result": result,
            "stderr_tail": proc.stderr[-1500:] if proc.returncode or
            result is None else ""}


def summary(rows: List[Dict]) -> Dict:
    by: Dict[str, Dict[int, List[float]]] = {}
    for r in rows:
        res = r["result"] or {}
        for name, m in res.get("metrics", {}).items():
            by.setdefault(name, {}).setdefault(r["set"], []).append(
                m["value"])
    out = {}
    for name, sets in by.items():
        out[name] = {f"set{k}": {"median": statistics.median(v),
                                 "spread": spread(v),
                                 "spread_trimmed": spread(trimmed(v))
                                 if len(v) > 2 else float("nan"),
                                 "values": v}
                     for k, v in sorted(sets.items())}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of one set")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "sets.jsonl"))
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []
    for set_no in range(1, args.sets + 1):
        for seed in seeds:
            row = one_run(args, seed, set_no)
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            res = row["result"] or {}
            print(json.dumps({"seed": seed, "set": set_no, "rc": row["rc"],
                              "wall_s": round(row["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {k: m["value"] for k, m in
                                          res.get("metrics", {}).items()},
                              "compared": {k: c["value"] for k, c in
                                           res.get("compared", {}).items()},
                              "info": res.get("info")}), flush=True)
            if row["stderr_tail"]:
                print(row["stderr_tail"], file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload,
                      "summary": summary(rows)}), flush=True)
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
