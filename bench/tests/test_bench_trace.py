"""The trace reduction, on hand-built traces with known answers and on a
slice of a trace recorded on a TPU v5e."""

from __future__ import annotations

import pytest

from bench import trace

#: host spans and device ops, nanoseconds from the line's timestamp 1000:
#: window [0, 100000); tick [10000, 60000) holding prescan [12000, 30000)
#: (market upload [12000, 14000)) and golden [35000, 55000); ops on the
#: device at [13000, 14000), [15000, 25000), [20000, 28000) (overlapping),
#: [40000, 50000) holding [42000, 46000) (a loop and an operation of its
#: body), and [70000, 75000) (between ticks)
SPANS = [("bench.window", 0, 100000), ("bench.tick", 10000, 50000),
         ("bench.prescan_call", 12000, 18000),
         ("bench.market_upload", 12000, 2000),
         ("bench.golden_call", 35000, 20000)]
OPS = [("copy.1", 13000, 1000), ("sort.2", 15000, 10000),
       ("fusion.3", 20000, 8000), ("while.4", 40000, 10000),
       ("fusion.6", 42000, 4000), ("fusion.5", 70000, 5000)]


#: program executions on the device: prescan's and golden's, and one
#: between ticks
MODULES = [("jit_run", 12000, 18000), ("jit_run", 35000, 20000),
           ("jit_run", 70000, 5000)]


def _proto(modules=()):
    def plane(pid, name, lines):
        names = sorted({n for _, events in lines for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = ""
        for lid, (line, events) in enumerate(lines, 1):
            evs = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000} }}\n" for n, s, d in events)
            body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 1000'
                     f'\n{evs}}}\n')
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'
    device = [("XLA Ops", OPS)] + ([("XLA Modules", modules)] if modules
                                   else [])
    return (plane(1, "/host:CPU", [("python", SPANS)])
            + plane(2, "/device:TPU:0", device))


def test_reduction_of_a_known_trace():
    from jax.profiler import ProfileData

    red = trace.reduce_profile(ProfileData.from_text_proto(_proto()))
    ns = 1e-9
    assert red.devices == 1
    assert red.window_s == pytest.approx(100000 * ns)
    # union: [13,14) + [15,28) + [40,50) + [70,75) thousand ns
    assert red.busy_s == pytest.approx(29000 * ns)
    assert red.busy_by_span["bench.prescan_call"] == pytest.approx(14000 * ns)
    assert red.busy_by_span["bench.market_upload"] == pytest.approx(1000 * ns)
    assert red.busy_by_span["bench.golden_call"] == pytest.approx(10000 * ns)
    assert red.busy_by_span["bench.tick"] == pytest.approx(24000 * ns)
    # each operation's own time: the overlap goes to the later operation,
    # and a loop's body operations are not the loop's own time
    ops = dict((k, v) for k, v in red.device_ops)
    assert ops["bench.prescan_call:sort.2"] == pytest.approx(5000 * ns)
    assert ops["bench.prescan_call:fusion.3"] == pytest.approx(8000 * ns)
    assert ops["bench.golden_call:while.4"] == pytest.approx(6000 * ns)
    assert ops["bench.golden_call:fusion.6"] == pytest.approx(4000 * ns)
    assert ops["bench.market_upload:copy.1"] == pytest.approx(1000 * ns)
    assert ops["bench.window:fusion.5"] == pytest.approx(5000 * ns)
    longest = red.idle_gaps[0]
    assert longest[0] == "bench.window"          # after the last op
    assert longest[1] == pytest.approx(25000 * ns)
    assert ["bench.tick", pytest.approx(12000 * ns)] in red.idle_gaps
    assert ["bench.prescan_call", pytest.approx(1000 * ns)] in red.idle_gaps


def test_no_window_span_reduces_to_nothing():
    from jax.profiler import ProfileData

    proto = _proto().replace('"bench.window"', '"other"')
    assert trace.reduce_profile(ProfileData.from_text_proto(proto)) is None


def test_operation_names_drop_their_hlo_text():
    assert trace.op_name("%while.164 = (s32[], u32[32]) while(...)") == \
        "while.164"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_busy_time_comes_from_program_executions():
    """Where the device plane has program executions, busy time is theirs:
    the profiler can lose a long program's operations, not the program."""
    from jax.profiler import ProfileData

    red = trace.reduce_profile(ProfileData.from_text_proto(
        _proto(MODULES)))
    ns = 1e-9
    assert red.busy_s == pytest.approx(43000 * ns)
    assert red.busy_ops_s == pytest.approx(29000 * ns)
    assert red.busy_by_span["bench.prescan_call"] == pytest.approx(18000 * ns)
    assert red.busy_by_span["bench.golden_call"] == pytest.approx(20000 * ns)
    # the breakdown still names single operations
    ops = dict((k, v) for k, v in red.device_ops)
    assert ops["bench.golden_call:fusion.6"] == pytest.approx(4000 * ns)
    assert red.idle_gaps[0] == ["bench.window", pytest.approx(25000 * ns)]


def test_reduction_of_a_recorded_v5e_trace():
    """One tick of ``karpenter_zone_m.tick32`` traced on a TPU v5e
    (``record_trace.py``): both programs run inside the backend's calls,
    busy time is their executions, and the operations' names are short."""
    import os

    from jax.profiler import ProfileData

    path = os.path.join(os.path.dirname(__file__), "v5e_tick32_slice.pbtxt")
    with open(path) as f:
        profile = ProfileData.from_text_proto(f.read())
    red = trace.reduce_profile(profile)
    assert red.devices == 1
    assert red.device_lines == ["XLA Modules", "XLA Ops"]
    [device] = [p for p in profile.planes if p.name.startswith("/device:")]
    [modules] = [ln for ln in device.lines if ln.name == "XLA Modules"]
    programs = [ev.duration_ns * 1e-9 for ev in modules.events]
    assert len(programs) == 2                    # prescan, then golden
    assert red.busy_s == pytest.approx(sum(programs))
    assert 0 < red.busy_s < red.window_s
    assert red.busy_by_span["bench.prescan_call"] == pytest.approx(
        programs[0])
    assert red.busy_by_span["bench.golden_call"] == pytest.approx(
        programs[1])
    for name, _secs in red.device_ops:
        label, op = name.split(":", 1)
        assert label in ("bench.prescan_call", "bench.golden_call")
        assert " = " not in op and not op.startswith("%")
    assert {label for label, _ in red.idle_gaps} <= {
        "bench.tick", "bench.prescan_call", "bench.golden_call"}
