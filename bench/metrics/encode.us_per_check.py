"""Host encode time per MCT check, over the batches with checks that the
window offered (their drain included): the host executor's own encode
stage (``StageTimes``) where the MCT wrapper serves, else the
benchmark's span around ``prepare_batch``."""


def read(run):
    if run.answers:
        times = [r.times for r in run.answers.values()]
        n = sum(t.batch for t in times)
        return sum(t.encode_us for t in times) / n if n else None
    calls = [(s, n) for s, n in run.taps.prepare if n > 0]
    if not calls:
        return None
    return sum(s for s, _ in calls) / sum(n for _, n in calls) * 1e6
