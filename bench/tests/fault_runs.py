"""Drive short CPU runs of the harness with the timed path broken
underneath, one fault per run, and print one JSON line per run:
``{"fault": name, "correct": bool, "compared": {...}}``.

    python bench/tests/fault_runs.py <spec.json> <config.json> <mix.json> \\
        <workload> <fault> [<fault> ...]

Faults (each patched into the program, not the harness):

* ``none`` — nothing broken: the run that must come out correct;
* ``control`` — nothing broken, but the comparison judges the control's
  answers (the reference with int32 costs) in place of the program's;
* ``answer_altered`` — every decision's pool gets one more node where the
  batched search returns it;
* ``half_batch`` — ``SolveBatch.execute`` solves the first half of its
  jobs and hands the rest copies of those decisions;
* ``stale_state`` — ``SolveBatch.execute`` hands every job the decision of
  the previous call, leaving the answers unchanged from tick to tick.
"""

from __future__ import annotations

import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _answer_altered():
    from repro.core import provisioner
    from repro.core.efficiency import NodePool

    orig = provisioner.bracketed_gss_many

    def altered(*args, **kwargs):
        out = []
        for pool, trace in orig(*args, **kwargs):
            if pool is not None and pool.counts:
                counts = list(pool.counts)
                counts[0] += 1
                pool = NodePool(items=pool.items, counts=counts,
                                alpha=pool.alpha, request=pool.request)
            out.append((pool, trace))
        return out

    provisioner.bracketed_gss_many = altered
    return lambda: setattr(provisioner, "bracketed_gss_many", orig)


def _patch_execute(after):
    from repro.core.provisioner import SolveBatch

    orig = SolveBatch.execute

    def execute(self):
        jobs = list(self._jobs)
        return after(self, jobs, orig)

    SolveBatch.execute = execute
    return lambda: setattr(SolveBatch, "execute", orig)


def _half_batch():
    def after(batch, jobs, orig):
        keep = jobs[:max(1, len(jobs) // 2)]
        batch._jobs = keep
        orig(batch)
        for job, src in zip(jobs[len(keep):], itertools.cycle(keep)):
            job.decision = src.decision
        return len(jobs)
    return _patch_execute(after)


def _stale_state():
    def after(batch, jobs, orig):
        n = orig(batch)
        fresh = [job.decision for job in jobs]
        prev = getattr(batch, "_stale", None)
        if prev is not None and len(prev) == len(jobs):
            for job, old in zip(jobs, prev):
                job.decision = old
        batch._stale = fresh
        return n
    return _patch_execute(after)


FAULTS = {"none": lambda: (lambda: None), "control": lambda: (lambda: None),
          "answer_altered": _answer_altered,
          "half_batch": _half_batch, "stale_state": _stale_state}


def main(argv):
    from bench import run

    spec, config, mix = (json.load(open(p)) for p in argv[:3])
    workload = argv[3]
    for fault in argv[4:]:
        undo = FAULTS[fault]()
        try:
            result = run.measure(workload, 20250101, 1.0, False,
                                 require_tpu=False, spec=spec, config=config,
                                 mix=mix, control=fault == "control")
        finally:
            undo()
        print(json.dumps({"fault": fault, "correct": result["correct"],
                          "compared": result["compared"]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
