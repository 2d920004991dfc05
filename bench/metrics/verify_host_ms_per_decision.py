"""Host time of the fused plane's prescan cross-check (one NumPy engine
solve of a sampled row per batch): self time of the program's
``kubepacs.fused.verify`` spans, over every decision the process has made
(warm-up, window and traced segment), per decision (program span, host
clock)."""

from bench.layers import span_self_ms_per_decision

SPANS = ("kubepacs.fused.verify",)


def read(run):
    return span_self_ms_per_decision(lambda name: name in SPANS)
