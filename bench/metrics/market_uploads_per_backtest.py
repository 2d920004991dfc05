"""Market uploads to the device per backtest: the growth of the backend's
device-market cache misses over the window, per backtest (program
counter)."""


def read(run):
    if run.kind != "backtest" or not run.requests:
        return None
    return run.counters.get("misses", 0) / run.requests
