"""Median wall time, in ms, of the program's ``lm.prefill`` spans in the
traced window: a batch's prompt stepped token by token through the
decode step, up to the host's read of its first generated token. None
where the trace holds no such span."""
import statistics

from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace["window_ns"]
    took = [e - s for s, e, _ in trace_reduce.host_spans(run.trace,
                                                         "lm.prefill")
            if lo <= s and e <= hi]
    return statistics.median(took) / 1e6 if took else None
