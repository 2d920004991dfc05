"""Device busy time inside the harness's spans around the backend's
golden-search calls, per decision of the traced segment (profiler trace)."""


def read(run):
    busy = None if run.trace is None else run.trace.busy_by_span.get(
        "bench.golden_call")
    if not busy or not run.traced_decisions:
        return None
    return busy / run.traced_decisions * 1e3
