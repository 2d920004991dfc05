"""Host time of the fleet engine: self time of the program's
``kubepacs.fleet.*`` spans (``run_fleet``, and ``FleetSim``'s set-up,
market refresh, interrupt sampling, collect, launch and precompile
phases), over every decision the process has made (warm-up, window and
traced segment), per decision (program span, host clock)."""

from bench.layers import span_self_ms_per_decision

PREFIX = "kubepacs.fleet."


def read(run):
    if run.kind != "backtest":
        return None
    return span_self_ms_per_decision(lambda name: name.startswith(PREFIX))
