"""95th percentile over every tick of the window of the time from the
tick's pending decisions being submitted to every pool being decided
(host clock)."""

import numpy as np


def read(run):
    if run.kind != "tick":
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
