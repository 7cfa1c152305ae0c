"""99th percentile of the query latencies of the window, each from its due
time to the result of its last sample, in ms."""
import numpy as np


def read(ctx):
    if ctx["mode"] != "serve" or not len(ctx["run"]["latency_s"]):
        return None
    return float(np.percentile(ctx["run"]["latency_s"], 99)) * 1e3
