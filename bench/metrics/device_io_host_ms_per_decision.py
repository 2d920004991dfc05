"""Host time around the device programs: self time of the program's
``kubepacs.device.inputs`` (shape key, per-decision arrays, the market
lookup) and ``kubepacs.device.readback`` (copies back, slicing, the replay
record's int lists) spans, over every decision the process has made
(warm-up, window and traced segment), per decision (program span, host
clock)."""

from bench.layers import span_self_ms_per_decision

SPANS = ("kubepacs.device.inputs", "kubepacs.device.readback")


def read(run):
    return span_self_ms_per_decision(lambda name: name in SPANS)
