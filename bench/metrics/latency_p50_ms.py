"""Median latency over every request due in the window, from due to resolved."""

import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 50) * 1e3) if lat.size else None
