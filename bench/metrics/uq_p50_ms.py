"""Median due-to-verdict latency over every user query due in the window
(host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 50)) if lat else None
