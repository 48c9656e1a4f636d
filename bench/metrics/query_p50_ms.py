"""Median query latency over all queries of the window, each timed from
its scheduled arrival to its result (a failed query counts as beyond any
limit)."""
import numpy as np


def read(run):
    if run.kind != "open_loop" or not run.window["attempted"]:
        return None
    return 1e3 * float(np.quantile(run.window["latencies_s"], 0.50))
