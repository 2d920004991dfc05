"""Host control time per decision: request wall minus the wall of the
backend's device calls (prescan and golden, each including its market
upload), over the window's decisions (host clock)."""

OUTER_CALLS = ("bench.prescan_call", "bench.golden_call")


def read(run):
    if not run.decisions:
        return None
    device_calls = sum(run.calls_wall.get(span, 0.0) for span in OUTER_CALLS)
    return (sum(run.latencies_s) - device_calls) / run.decisions * 1e3
