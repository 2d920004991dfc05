"""Host time of the search's replay: self time of the program's
``kubepacs.gss`` spans (``bracketed_gss_many``: lockstep replay, exact
scoring, bracket choice and epilogue, less the device calls and the
cross-check nested in it) and ``kubepacs.decision.finish`` spans
(building each decision of a ``SolveBatch``), over every decision the
process has made (warm-up, window and traced segment), per decision
(program span, host clock)."""

from bench.layers import span_self_ms_per_decision

SPANS = ("kubepacs.gss", "kubepacs.decision.finish")


def read(run):
    return span_self_ms_per_decision(lambda name: name in SPANS)
