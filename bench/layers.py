#!/usr/bin/env python3
"""The program's own layers, read from its spans and stage scopes.

The program times its hot path with ``kubepacs.*`` spans
(``repro.core.events_log.span``), names its two device programs
(``jit_kubepacs_prescan``, ``jit_kubepacs_golden``) and puts each stage of
them under a ``jax.named_scope`` (``STAGES``; DESIGN.md §13).  The TPU
trace names an operation by its HLO instruction without metadata, so an
operation's scope path (op_name) comes from the compiled programs' HLO
text (``compiled_op_paths``).  Two readings live here:

* ``span_self_ms_per_decision``: the self time of chosen spans from the
  program's in-memory aggregates (``events_log.span_totals()``), summed
  over the process, per decision (one ``kubepacs.provision`` span each).
  The host-clock layer readers of ``bench/metrics/`` share it.
* ``reduce_layers``: a traced segment (the ``.xplane.pb`` of a ``--trace
  1`` run) reduced to the program's layers, as ``bench/trace.py`` reduces
  it to the harness's ``bench.*`` spans: per ``program/stage`` the
  device's own time, per span its wall, the device's busy time inside it
  and (program spans) its self time, the device's idle time under each
  innermost span, and the longest operations and idle gaps named by the
  innermost span of either prefix.

    python3 bench/layers.py --workload <cell> --seed <n> [--seconds 10]

runs one traced run of the cell (``bench/run.py``'s ``measure``, on the
chip), builds the cell's programs again from the compile cache for their
HLO text, reduces the traced segment and prints one JSON line: the run's
result and the layers, with the per-decision readings of the segment
(decisions counted by its ``kubepacs.provision`` spans).
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import os
import re
import sys
import tempfile
from typing import Callable, Dict, List, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

Event = Tuple[float, float, str]

PROGRAM_PREFIX = "kubepacs."
SPAN_PREFIXES = (trace.SPAN_PREFIX, PROGRAM_PREFIX)
#: one per decision a provisioner makes (memo hits included)
DECISION_SPAN = "kubepacs.provision"
#: the program's spans around its two device programs (call to ready)
DEVICE_CALL_SPANS = ("kubepacs.device.prescan", "kubepacs.device.golden")
#: the named scopes of the device programs' stages
STAGES = frozenset({"saturate", "sort", "lp_prune", "core_dp", "compact",
                    "cover_dp", "backtrack", "score", "control", "rows"})
COVER_DP = ("core_dp", "cover_dp")
PRUNE = ("sort", "lp_prune", "compact")
UNSCOPED = "unscoped"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"\s*(ROOT )?%\S+ = ")


def span_self_ms_per_decision(match: Callable[[str], bool]
                              ) -> Optional[float]:
    """Self time of the program's spans whose name ``match``\\ es, summed
    over the process so far, per decision; None where the program keeps
    no spans (or none of these ran)."""
    from repro.core import events_log

    totals = getattr(events_log, "span_totals", None)
    if totals is None:
        return None
    spans = totals()
    decisions = spans.get(DECISION_SPAN, (0,))[0]
    mine = [agg[2] for name, agg in spans.items() if match(name)]
    if not decisions or not mine:
        return None
    return sum(mine) * 1e-6 / decisions


# -- the traced segment -------------------------------------------------------

def instruction_key(program: str, text: str) -> str:
    """An HLO instruction of a program by what the trace's name for it
    and the compiled HLO text share: its name and its result type (the
    trace prints operands with their types, the compiled text without)."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, _, rest = text.partition(" = ")
    if rest.startswith("("):            # a tuple type: to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        kind = rest[:i + 1]
    else:
        kind = rest.split(" ", 1)[0]
    return f"{program} {name} = {kind}"


def hlo_op_paths(hlo_text: str) -> Dict[str, str]:
    """``instruction_key`` → op_name of each instruction of a compiled HLO
    text that has one; "" where two instructions share a key."""
    out: Dict[str, str] = {}
    program = ""
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            program = program_name(line.split()[1].rstrip(","))
        elif _INSTRUCTION.match(line):
            m = _OP_NAME.search(line)
            if m:
                key = instruction_key(program, line)
                out[key] = m.group(1) if out.get(key, m.group(1)) == \
                    m.group(1) else ""
    return out


def _program_args(key) -> tuple:
    """Arguments of the shapes and types a program of ``key`` (the fused
    backend's ``(kind, N, B, RC, D, G or MAXR)``) is called with."""
    import numpy as np

    kind, N, B, _RC, D, last = key
    i32, i64, f32 = np.int32, np.int64, np.float32
    market = (np.zeros(N, i32), np.zeros(N, i32), np.zeros(B, i32),
              np.zeros(B, i32), np.zeros(B, i32), np.zeros(B, bool),
              np.zeros(N, f32), np.zeros(N, f32))
    decisions = (np.zeros((D, N), i64), np.zeros((D, N), i64),
                 np.zeros((D, N), bool), np.zeros(D, i64))
    tail = ((np.zeros(last, i64),) if kind == "prescan"
            else (np.zeros(D, i64), np.zeros(D, i64), np.int64(0)))
    return (market, *decisions, *tail, np.zeros(3, i64))


def cell_files(workload: str) -> Tuple[Dict, Dict]:
    """The configuration and the mix a cell of ``BENCHMARK.json`` runs."""
    from bench import run

    cell = run.find_cell(run.load_json(os.path.join(ROOT, "BENCHMARK.json")),
                         workload)
    return (run.load_json(os.path.join(BENCH, "configs",
                                       cell["config"] + ".json")),
            run.load_json(os.path.join(BENCH, "traffic",
                                       cell["traffic"] + ".json")))


def compiled_op_paths(config: Dict, mix: Dict, seed: int
                      ) -> Dict[str, str]:
    """``instruction_key`` → op_name over every device program a cell's
    warm-up builds: a fresh backend serves the warm-up (its programs load
    from the compile cache a run of the cell filled), then each program is
    lowered and compiled again at its shapes for its HLO text."""
    import numpy as np

    from bench import cells
    from repro.core import make_backend

    backend = make_backend("jax:fused")
    unit = cells.KINDS[mix["kind"]](config, mix, seed, backend)
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(3)[0])
    for req in unit.warm_requests(rng):
        unit.serve(req)
    out: Dict[str, str] = {}
    for key, fn in backend._fused_cache.items():
        out.update(hlo_op_paths(
            fn.lower(*_program_args(key)).compile().as_text()))
    return out


def op_path(name: str, program: str, op_paths: Dict[str, str]) -> str:
    """An operation's op_name (its scope path, ``jit(kubepacs_golden)/
    control/while/body/...``): from the metadata of its name where the
    name carries one (a recorded slice), else from the compiled programs'
    text; "" where neither has it."""
    m = _OP_NAME.search(name)
    if m:
        return m.group(1)
    return op_paths.get(instruction_key(program, name), "")


def stage(path: str) -> str:
    """The innermost ``STAGES`` scope of an op_name path (its last part
    names the operation itself), or ""."""
    for part in reversed(path.split("/")[:-1]):
        if part in STAGES:
            return part
    return ""


def program_name(module: str) -> str:
    """``jit_kubepacs_golden(1234)`` → ``kubepacs_golden``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


class _Labels(trace._Labels):
    """Innermost span at any instant, and the cut of an interval by it."""

    def split(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """``[lo, hi)`` cut where the innermost span changes: each piece's
        label and length."""
        out = []
        i = bisect.bisect_right(self._cuts, lo) - 1
        t = lo
        while t < hi:
            end = (min(self._cuts[i + 1], hi) if i + 1 < len(self._cuts)
                   else hi)
            out.append(((self._label[i] if i >= 0 else "") or "outside",
                        end - t))
            t, i = end, i + 1
        return out


class _Programs:
    """The program execution (``XLA Modules``) that holds an instant."""

    def __init__(self, modules: Sequence[Event]):
        self._mods = sorted(modules)
        self._starts = [s for s, _e, _n in self._mods]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._mods[i][1]:
            return program_name(self._mods[i][2])
        return ""


def _nesting(events: Sequence[Event], w0: float, w1: float) -> Tuple[
        List[Event], List[float], List[int]]:
    """Events of one line clipped to ``[w0, w1)``, each one's own time
    (its length less that of the events nested in it, as
    ``bench.trace._self_times`` counts it) and the index of the event it
    is nested in (-1: none)."""
    clipped = sorted(((max(s, w0), min(e, w1), n) for s, e, n in events
                      if min(e, w1) > max(s, w0)),
                     key=lambda ev: (ev[0], -ev[1]))
    own = [e - s for s, e, _ in clipped]
    parents = [-1] * len(clipped)
    stack: List[int] = []
    for i, (s, e, _n) in enumerate(clipped):
        while stack and clipped[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, clipped[stack[-1]][1]) - s
            parents[i] = stack[-1]
        stack.append(i)
    return clipped, own, parents


@dataclasses.dataclass
class Layers:
    """A traced segment by the program's layers (seconds; the device
    numbers averaged over the devices that ran anything)."""

    window_s: float
    busy_s: float
    decisions: int                    # kubepacs.provision spans in it
    stage_s: Dict[str, float]         # "program/stage" -> device own time
    span_wall_s: Dict[str, float]     # span name -> wall in the segment
    span_busy_s: Dict[str, float]     # span name -> device busy inside
    span_self_s: Dict[str, float]     # program span -> wall less nested
    idle_by_span: Dict[str, float]    # innermost span -> device idle
    device_ops: List[List]
    idle_gaps: List[List]

    def per_decision_ms(self, secs: float) -> Optional[float]:
        return secs / self.decisions * 1e3 if self.decisions else None

    def readings(self) -> Dict[str, Optional[float]]:
        """The segment's layer readings, ms per decision, and shares."""
        stages = {key.split("/", 1)[-1] for key in self.stage_s}

        def stage_sum(names):
            return sum(v for key, v in self.stage_s.items()
                       if key.split("/", 1)[-1] in names)
        calls = [n for n in DEVICE_CALL_SPANS if n in self.span_wall_s]
        requests = sum(v for n, v in self.span_wall_s.items()
                       if n in ("bench.tick", "bench.backtest"))
        out = {
            "dispatch_overhead_ms_per_decision": self.per_decision_ms(
                sum(self.span_wall_s[n] - self.span_busy_s.get(n, 0.0)
                    for n in calls)) if calls else None,
            "cover_dp_device_ms_per_decision": self.per_decision_ms(
                stage_sum(COVER_DP)) if stages & set(COVER_DP) else None,
            "prune_device_ms_per_decision": self.per_decision_ms(
                stage_sum(PRUNE)) if stages & set(PRUNE) else None,
            "span_cover_share": (sum(self.span_self_s.values()) / requests
                                 if requests and self.span_self_s
                                 else None),
            "idle_share_under_program_spans": (
                sum(v for n, v in self.idle_by_span.items()
                    if n.startswith(PROGRAM_PREFIX))
                / sum(self.idle_by_span.values())
                if self.idle_by_span else None)}
        totals: Dict[str, float] = {}
        for key, secs in self.stage_s.items():
            prog = key.split("/", 1)[0]
            totals[prog] = totals.get(prog, 0.0) + secs
        for prog, total in totals.items():
            if total > 0:
                out[f"{prog}.unscoped_share"] = self.stage_s.get(
                    f"{prog}/{UNSCOPED}", 0.0) / total
        for name, secs in self.span_self_s.items():
            out[f"{name}.self_ms_per_decision"] = self.per_decision_ms(secs)
        return out


def _collect(profile) -> Tuple[Dict[str, List[Event]],
                               Dict[str, Dict[str, List[Event]]]]:
    """Per host line its spans of either prefix; per device plane the
    events of its ``XLA Modules`` and ``XLA Ops`` lines."""
    spans: Dict[str, List[Event]] = {}
    lines: Dict[str, Dict[str, List[Event]]] = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in trace.DEVICE_LINES:
                    lines.setdefault(plane.name, {})[line.name] = [
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events
                        if ev.name.startswith(SPAN_PREFIXES)]
                if mine:
                    spans[f"{plane.name}/{line.name}"] = mine
    return spans, lines


def reduce_layers(profile, op_paths: Optional[Dict[str, str]] = None
                  ) -> Optional[Layers]:
    """Reduce a ``jax.profiler.ProfileData``, with the op_name of each
    instruction (``compiled_op_paths``) where the trace's names carry
    none; None where the trace holds no ``bench.window`` span or no device
    operation inside it."""
    by_line, lines = _collect(profile)
    op_paths = op_paths or {}
    spans = [sp for line in by_line.values() for sp in line]
    windows = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    window = [(w0, w1)]

    def busy(events):
        return trace.union([(max(s, w0), min(e, w1)) for s, e, _ in events])

    per_device = {dev: busy(trace._first(by, trace.BUSY_LINES))
                  for dev, by in lines.items()}
    per_device = {dev: u for dev, u in per_device.items() if u}
    if not per_device:
        return None
    n_dev = len(per_device)
    busy_s = sum(trace.overlap(u, window) for u in per_device.values()
                 ) / n_dev

    inner = [sp for sp in spans if sp[2] != trace.WINDOW_SPAN]
    span_wall_s, span_busy_s = {}, {}
    for name in sorted({n for _, _, n in inner}):
        mine = trace.union([(s, e) for s, e, n in inner if n == name])
        span_wall_s[name] = trace.overlap(mine, window)
        span_busy_s[name] = sum(trace.overlap(u, mine)
                                for u in per_device.values()) / n_dev
    span_self_s: Dict[str, float] = {}
    for line in by_line.values():
        program = [sp for sp in line if sp[2].startswith(PROGRAM_PREFIX)]
        clipped, own, _parents = _nesting(program, w0, w1)
        for (_s, _e, name), secs in zip(clipped, own):
            span_self_s[name] = span_self_s.get(name, 0.0) + secs
    decisions = sum(1 for s, _e, n in spans
                    if n == DECISION_SPAN and w0 <= s < w1)

    labels = _Labels(spans)
    op_time: Dict[Tuple[str, str], float] = {}
    stage_s: Dict[str, float] = {}
    own_stage: Dict[Tuple[str, str], str] = {}
    for dev in per_device:
        programs = _Programs(lines[dev].get("XLA Modules", ()))
        ops = trace._first(lines[dev], trace.OPS_LINES)
        clipped, owns, parents = _nesting(ops, w0, w1)
        stages: List[str] = []
        for (s, _e, name), own, parent in zip(clipped, owns, parents):
            prog = programs.at(s)
            if (prog, name) not in own_stage:
                own_stage[(prog, name)] = stage(op_path(name, prog,
                                                        op_paths))
            # an operation with no stage of its own runs in that of the
            # loop or branch that holds it
            st = own_stage[(prog, name)] or (
                stages[parent] if parent >= 0 else "")
            stages.append(st)
            op = trace.op_name(name)
            key = (labels.at(s), f"{prog}/{st}/{op}" if st else op)
            op_time[key] = op_time.get(key, 0.0) + own / n_dev
            if prog:
                sk = f"{prog}/{st or UNSCOPED}"
                stage_s[sk] = stage_s.get(sk, 0.0) + own / n_dev
    device_ops = [[f"{label}:{name}", v] for (label, name), v in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:trace.TOP]]

    gaps: List[Tuple[float, str]] = []
    idle_by_span: Dict[str, float] = {}
    for u in per_device.values():
        edges = [w0] + [t for seg in u for t in seg] + [w1]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((hi - lo, labels.at((lo + hi) / 2)))
                for label, length in labels.split(lo, hi):
                    idle_by_span[label] = (idle_by_span.get(label, 0.0)
                                           + length / n_dev)
    gaps.sort(key=lambda g: -g[0])
    return Layers(window_s=w1 - w0, busy_s=busy_s, decisions=decisions,
                  stage_s=stage_s, span_wall_s=span_wall_s,
                  span_busy_s=span_busy_s, span_self_s=span_self_s,
                  idle_by_span=idle_by_span, device_ops=device_ops,
                  idle_gaps=[[n, g] for g, n in gaps[:trace.TOP]])


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    from bench import run

    with tempfile.TemporaryDirectory() as tmp:
        xplane = os.path.join(tmp, "trace.xplane.pb")
        try:
            result = run.measure(args.workload, args.seed, args.seconds,
                                 True, keep_trace=xplane)
        except run.BenchError as exc:
            print(f"bench: {exc}", file=sys.stderr)
            return 1
        config, mix = cell_files(args.workload)
        layers = reduce_layers(ProfileData.from_file(xplane),
                               compiled_op_paths(config, mix, args.seed))
    out = {"result": result}
    if layers is not None:
        out["layers"] = dataclasses.asdict(layers)
        out["readings"] = layers.readings()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
