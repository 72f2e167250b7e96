"""Mean device time of one run of the jitted decode step in the traced
window (profiler)."""
from bench import trace_reduce


def read(run):
    if run.trace is None or "decode" not in run.modules:
        return None
    runs = trace_reduce.module_runs(run.trace, run.modules["decode"])
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6
