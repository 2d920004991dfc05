"""Decisions delivered over the whole window, memo hits included
(host clock): the fleet's decision throughput."""


def read(run):
    return run.decisions / run.window_s
