"""Record a slice of a real TPU trace of one cell, for the trace
reduction's test (``test_bench_trace.py``).

    python3 bench/tests/record_trace.py <workload> <seed> <out.pbtxt>

Runs the cell for 1 s with ``--trace 1`` on the chip, keeps the traced
segment's ``.xplane.pb`` and writes a slice of it as a text ``XSpace``: the
first ``bench.tick`` of the segment, with the ``bench.*`` host spans that
start in it, a ``bench.window`` span over it, every program execution on
the device (``XLA Modules``) and its first ``MAX_OPS`` operations (``XLA
Ops``).  Operation names are cut to ``NAME_CHARS`` characters (the
TPU names an operation by its whole HLO instruction).
"""

from __future__ import annotations

import os
import sys
import tempfile
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MAX_OPS = 1500
NAME_CHARS = 160
DEVICE_LINES = ("XLA Modules", "XLA Ops")

Event = Tuple[float, float, str]      # start ns, end ns, name


def _plane_text(pid: int, name: str, lines: List[Tuple[str, List[Event]]],
                t0: int) -> str:
    names = sorted({n for _, events in lines for _, _, n in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (line, events) in enumerate(lines, 1):
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: {t0}')
        out.extend(f"events {{ metadata_id: {ids[n]} offset_ps: "
                   f"{round((s - t0) * 1000)} duration_ps: "
                   f"{round((e - s) * 1000)} }}" for s, e, n in events)
        out.append("}")
    out.extend(f'event_metadata {{ key: {i} value {{ id: {i} name: '
               f'"{n}" }} }}' for n, i in ids.items())
    out.append("}")
    return "\n".join(out) + "\n"


def slice_text(profile) -> str:
    spans: List[Event] = []
    device: Dict[str, List[Event]] = {}
    device_plane = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events
                             if ev.name.startswith("bench."))
        elif plane.name.startswith("/device:") and device_plane is None:
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    device[line.name] = sorted(
                        (ev.start_ns, ev.end_ns,
                         ev.name.replace('"', "'")[:NAME_CHARS])
                        for ev in line.events)
            device_plane = plane.name if device else None
    w0, w1, _ = min(s for s in spans if s[2] == "bench.tick")
    host = [(w0, w1, "bench.window")] + [
        sp for sp in spans if w0 <= sp[0] < w1 and sp[2] != "bench.window"]
    # every program execution of the tick; its operations only up to the
    # MAX_OPS-th (a tick runs about a million)
    lines = [(name, [ev for ev in device[name] if w0 <= ev[0] < w1])
             for name in DEVICE_LINES]
    lines[1] = (lines[1][0], lines[1][1][:MAX_OPS])
    t0 = int(w0)
    return (_plane_text(1, "/host:CPU", [("python", sorted(host))], t0)
            + _plane_text(2, device_plane, lines, t0))


def main() -> int:
    workload, seed, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    from bench import run

    with tempfile.TemporaryDirectory() as tmp:
        xplane = os.path.join(tmp, "trace.xplane.pb")
        result = run.measure(workload, seed, 1.0, True, keep_trace=xplane)
        from jax.profiler import ProfileData
        text = slice_text(ProfileData.from_file(xplane))
    with open(out, "w") as f:
        f.write(text)
    print({"correct": result["correct"], "bytes": len(text)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
