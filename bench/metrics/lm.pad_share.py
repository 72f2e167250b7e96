"""Share of the decode step's row slots, in %, that carried no real
token: pad rows of the power-of-two batch bucket, the lead-in steps of
prompts shorter than their batch's longest, and steps of rows that
already had their tokens. 1 - real tokens / (padded rows x steps), over
the batches of the queries scored in the window. Each completion
carries its batch's ``rows_padded`` and ``steps`` and takes a
1/``batch_size`` part of the slots; its real
tokens are its prompt and every generated token but the last. None where
no completion carries them."""
import math


def read(run):
    tokens = slots = 0
    for rid, c in run.completions.items():
        steps = getattr(c, "steps", 0)
        t = run.verdict.get(rid, (math.inf,))[0]
        if not steps or not run.t0 <= t <= run.t1:
            continue
        q = run.traffic.queries[run.query_of[rid]]
        tokens += len(q.prompt) + max(len(c.tokens) - 1, 0)
        slots += c.rows_padded * steps / c.batch_size
    return 100.0 * (1.0 - tokens / slots) if slots else None
