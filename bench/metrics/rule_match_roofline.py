"""Share of the bytes-bound least time in the match program's device time.

Each run of the match program in the traced window is paired with the
benchmark's host span around the call that launched it (the latest one
that began before it); the span carries the call's number of checks. The
least time of a call is the bytes any exact matcher has to move (the
compiled table's bounds and weights read once, the checks and results)
over the device's peak memory bandwidth.
"""
from bench import flops, trace_reduce


def read(run):
    if run.trace is None or "match" not in run.modules:
        return None
    runs = trace_reduce.module_runs(run.trace, run.modules["match"])
    spans = trace_reduce.host_spans(run.trace, "bench.match")
    if not runs or not spans:
        return None
    cols, rows = run.table_shape
    bw = run.peaks()["hbm_bytes_per_s"]
    least = took = 0.0
    for s, e in runs:
        before = [sp for sp in spans if sp[0] <= s]
        if not before or "checks" not in before[-1][2]:
            continue
        q = int(before[-1][2]["checks"])
        least += flops.match_bytes(q, rows, cols) / bw
        took += (e - s) / 1e9
    return 100.0 * least / took if took > 0 else None
