"""Median time a scored user query waited between its arrival and the
start of its batch's host encode, from the session's MetricsCollector."""


def read(run):
    q = run.report.breakdown["queue_wait"]
    return q.p50_ms if q.n else None
