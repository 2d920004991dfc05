"""95th percentile over every backtest of the window of its wall time
(host clock)."""

import numpy as np


def read(run):
    if run.kind != "backtest":
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
