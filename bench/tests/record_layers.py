"""Record a slice of a real TPU trace of one cell with the program's own
spans and stage scopes, for the layer reduction's test
(``test_bench_layers.py``).

    python3 bench/tests/record_layers.py <workload> <seed> <out.pbtxt>

Runs the cell for 1 s with ``--trace 1`` on the chip, keeps the traced
segment's ``.xplane.pb`` and writes a slice of it as a text ``XSpace``,
as ``record_trace.py`` does: the first ``bench.tick`` of the segment, now
with the ``bench.*`` and ``kubepacs.*`` host spans that start in it, a
``bench.window`` span over it, every program execution on the device
(``XLA Modules``) and the first ``MAX_OPS`` operations (``XLA Ops``) of
each.  An operation keeps its name cut to ``NAME_CHARS`` characters and
its op_name path, from the compiled programs' HLO text
(``bench.layers.compiled_op_paths``), written back as the instruction's
``metadata``.
"""

from __future__ import annotations

import bisect
import os
import sys
import tempfile
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.tests import record_trace  # noqa: E402
from bench.tests.record_trace import Event  # noqa: E402

MAX_OPS = 1500
NAME_CHARS = record_trace.NAME_CHARS


def _quoted(name: str) -> str:
    """A name as a text proto string holds it."""
    return name.replace("\\", "\\\\").replace('"', '\\"')


def short_name(name: str, path: str) -> str:
    """An operation's HLO text cut short, with its op_name path."""
    head = name.split(", metadata=", 1)[0][:NAME_CHARS]
    return f'{head}, metadata={{op_name="{path}"}}' if path else head


def slice_text(profile, op_paths: Dict[str, str]) -> str:
    from bench import layers

    spans: List[Event] = []
    device: Dict[str, List[Event]] = {}
    device_plane = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events
                             if ev.name.startswith(layers.SPAN_PREFIXES))
        elif plane.name.startswith("/device:") and device_plane is None:
            for line in plane.lines:
                if line.name in record_trace.DEVICE_LINES:
                    device[line.name] = sorted(
                        (ev.start_ns, ev.end_ns, ev.name)
                        for ev in line.events)
            device_plane = plane.name if device else None
    w0, w1, _ = min(s for s in spans if s[2] == "bench.tick")
    host = [(w0, w1, "bench.window")] + [
        sp for sp in spans if w0 <= sp[0] < w1 and sp[2] != "bench.window"]
    # every program execution of the tick; of each, its operations only up
    # to the MAX_OPS-th (a tick runs about a million), named with their
    # op_name
    modules = [ev for ev in device["XLA Modules"] if w0 <= ev[0] < w1]
    ops = device["XLA Ops"]
    kept: List[Event] = []
    for m0, m1, module in modules:
        program = layers.program_name(module)
        i = bisect.bisect_left(ops, (m0,))
        kept.extend((s, e, _quoted(short_name(n, layers.op_path(
            n, program, op_paths)))) for s, e, n in ops[i:i + MAX_OPS]
            if s < m1)
    t0 = int(w0)
    return (record_trace._plane_text(1, "/host:CPU",
                                     [("python", sorted(host))], t0)
            + record_trace._plane_text(2, device_plane,
                                       [("XLA Modules", modules),
                                        ("XLA Ops", kept)], t0))


def main() -> int:
    workload, seed, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    from jax.profiler import ProfileData

    from bench import layers, run

    with tempfile.TemporaryDirectory() as tmp:
        xplane = os.path.join(tmp, "trace.xplane.pb")
        result = run.measure(workload, seed, 1.0, True, keep_trace=xplane)
        text = slice_text(ProfileData.from_file(xplane),
                          layers.compiled_op_paths(
                              *layers.cell_files(workload), seed))
    with open(out, "w") as f:
        f.write(text)
    print({"correct": result["correct"], "bytes": len(text)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
