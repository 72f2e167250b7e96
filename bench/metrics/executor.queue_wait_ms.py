"""Median time a batch waited in the MCT host executor's input queue, in
ms: the wrapper's own queue stage (``StageTimes.queue_us``) over the
batches the window submitted. None where the trace holds no ``mct.*``
span of the program's."""
import statistics


def read(run):
    if run.trace is None or not run.answers or not any(
            e[0].startswith("mct.") for e in run.trace["host"]):
        return None
    return statistics.median(r.times.queue_us
                             for r in run.answers.values()) / 1e3
