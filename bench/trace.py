"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced run wraps its measured window in a ``bench.window`` host span
and every call into a layer in a ``bench.*`` span (``jax.profiler.
TraceAnnotation``), so device time can be attributed to what the host was
doing without names from inside the program.  From the ``.xplane.pb``:

* device busy: the union of the intervals in which a program ran on each
  device plane (``/device:TPU:n``, line ``XLA Modules``, else ``XLA
  Ops``), clipped to the window and averaged over the devices that ran
  anything;
* busy per span name: the part of that union inside spans of the name
  (inclusive of spans nested in them);
* ``breakdown``: the device operations that took most time (each its own
  time, less the operations nested in it), each named by its innermost
  enclosing ``bench.*`` span, and the longest idle gaps, named by the
  innermost span open at the gap's middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: device lines in order of preference: busy time is taken from whole
#: program executions, the breakdown from single operations (the TPU
#: profiler can lose operations of a long program, never the program)
BUSY_LINES = ("XLA Modules", "XLA Ops")
OPS_LINES = ("XLA Ops", "XLA Modules")
DEVICE_LINES = frozenset(BUSY_LINES)
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Reduction:
    """What the per-layer readers and the result line take from a trace
    (seconds)."""

    window_s: float
    busy_s: float
    devices: int
    busy_by_span: Dict[str, float]
    device_ops: List[List]
    idle_gaps: List[List]
    busy_ops_s: float                 # the union of single operations
    device_lines: List[str]           # the lines of the device planes


class _Labels:
    """Innermost ``bench.*`` span at any instant.  Spans are properly
    nested, so the innermost open span is the latest-starting one, and of
    two that start together the one that ends first."""

    def __init__(self, spans: Sequence[Tuple[float, float, str]]):
        # one sweep over the span edges with a stack of open spans: at each
        # cut, spans ending there close, then those starting there open,
        # outermost first; the segment from the cut on is labelled by the
        # top of the stack
        starts: Dict[float, List[Tuple[float, int]]] = {}
        ends: Dict[float, List[int]] = {}
        for i, (s, e, _name) in enumerate(spans):
            if e > s:
                starts.setdefault(s, []).append((e, i))
                ends.setdefault(e, []).append(i)
        self._cuts = sorted(set(starts) | set(ends))
        self._label: List[str] = []
        stack: List[int] = []
        for t in self._cuts:
            for i in ends.get(t, ()):
                stack.pop(len(stack) - 1 - stack[::-1].index(i))
            for _e, i in sorted(starts.get(t, ()), reverse=True):
                stack.append(i)
            self._label.append(spans[stack[-1]][2] if stack else "")

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self._cuts, t) - 1
        if 0 <= i < len(self._label):
            return self._label[i] or "outside"
        return "outside"


def _spans_and_lines(profile) -> Tuple[
        List[Tuple[float, float, str]],
        Dict[str, Dict[str, List[Tuple[float, float, str]]]], List[str]]:
    """The ``bench.*`` host spans, per device plane the events of each line
    of ``DEVICE_LINES`` it has, and the names of all device lines."""
    spans: List[Tuple[float, float, str]] = []
    lines: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    line_names: set = set()
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                line_names.add(line.name)
                if line.name in DEVICE_LINES:
                    lines.setdefault(plane.name, {})[line.name] = [
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return spans, lines, sorted(line_names)


def _first(by_line: Dict[str, List], names: Sequence[str]) -> List:
    for name in names:
        if by_line.get(name):
            return by_line[name]
    return []


def op_name(name: str) -> str:
    """An operation's name without its HLO text: the TPU trace names an
    operation by its whole instruction (``%while.164 = (s32[], ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(ops: Sequence[Tuple[float, float, str]], w0: float,
                w1: float) -> Tuple[List[Tuple[float, float, str]],
                                    List[float]]:
    """The operations clipped to the window, and each one's own time: its
    length less that of the operations nested in it (a ``while`` holds the
    operations of its body, and they appear on the same line)."""
    clipped = sorted(((max(s, w0), min(e, w1), n) for s, e, n in ops
                      if min(e, w1) > max(s, w0)),
                     key=lambda op: (op[0], -op[1]))
    own = [e - s for s, e, _ in clipped]
    stack: List[int] = []
    for i, (s, e, _n) in enumerate(clipped):
        while stack and clipped[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, clipped[stack[-1]][1]) - s
        stack.append(i)
    return clipped, own


def reduce_profile(profile) -> Optional[Reduction]:
    """Reduce a ``jax.profiler.ProfileData``; None where the trace holds no
    ``bench.window`` span or no device operation inside it."""
    spans, lines, line_names = _spans_and_lines(profile)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    window = [(w0, w1)]

    def busy(events):
        return union([(max(s, w0), min(e, w1)) for s, e, _ in events])

    per_device = {dev: busy(_first(by_line, BUSY_LINES))
                  for dev, by_line in lines.items()}
    per_device = {dev: u for dev, u in per_device.items() if u}
    if not per_device:
        return None
    busy_s = sum(overlap(u, window) for u in per_device.values()) / len(
        per_device)
    ops_by_device = {dev: _first(by_line, OPS_LINES)
                     for dev, by_line in lines.items() if dev in per_device}
    busy_ops_s = sum(overlap(busy(ops), window)
                     for ops in ops_by_device.values()) / len(per_device)

    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    names = sorted({name for _, _, name in inner})
    busy_by_span = {}
    for name in names:
        mine = union([(s, e) for s, e, n in inner if n == name])
        busy_by_span[name] = sum(overlap(u, mine) for u in per_device.values()
                                 ) / len(per_device)

    labels = _Labels(spans)
    op_time: Dict[Tuple[str, str], float] = {}
    for ops in ops_by_device.values():
        for (s, _e, name), own in zip(*_self_times(ops, w0, w1)):
            key = (labels.at(s), op_name(name))
            op_time[key] = op_time.get(key, 0.0) + own / len(per_device)
    device_ops = [[f"{label}:{name}", v] for (label, name), v in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:TOP]]

    gaps: List[Tuple[float, str]] = []
    for u in per_device.values():
        edges = [w0] + [t for seg in u for t in seg] + [w1]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((hi - lo, labels.at((lo + hi) / 2)))
    gaps.sort(key=lambda g: -g[0])
    idle_gaps = [[name, length] for length, name in gaps[:TOP]]
    return Reduction(window_s=w1 - w0, busy_s=busy_s,
                     devices=len(per_device), busy_by_span=busy_by_span,
                     device_ops=device_ops, idle_gaps=idle_gaps,
                     busy_ops_s=busy_ops_s, device_lines=line_names)
