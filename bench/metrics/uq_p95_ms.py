"""95th percentile of due-to-verdict latency over every user query due in
the window (host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 95)) if lat else None
