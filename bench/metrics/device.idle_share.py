"""Share of the traced window in which no program ran on the device
(profiler), in %."""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    w = trace_reduce.window_s(run.trace)
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace) / w) if w else None
