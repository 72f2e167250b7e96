"""The LM serving path's phase spans on the profiler's clock: a batch's
MCT filter, its prompt steps and its generation steps, each with the
row counts its args promise."""
import glob
import os
import sys
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.compiler import compile_rules
from repro.core.engine import ErbiumEngine
from repro.core.rules import generate_queries, generate_rules
from repro.serve.engine import LMServer, Request
from repro.serve.trace import LM_SPANS

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace_reduce  # noqa: E402


def _span_args(log_dir):
    """name -> [args] of every host span of the LM path, in start order."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {name: [] for name in LM_SPANS.values()}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns, dict(e.stats)))
    return {k: [a for _, a in sorted(v, key=lambda x: x[0])]
            for k, v in out.items()}


def test_lm_phase_spans_and_their_args(tmp_path):
    rs = generate_rules(150, version=2, seed=3)
    engine = ErbiumEngine(compile_rules(rs), backend="ref")
    srv = LMServer(get_config("llama3.2-3b").reduced(), max_seq=32,
                   rule_filter=engine)
    qs = generate_queries(rs, 6, seed=4)
    rng = np.random.default_rng(0)
    # three prompts of 2, 5 and 9 tokens (a bucket of four rows); the
    # first wants 3 tokens, the others 2
    reqs = [Request(rid=i, tokens=rng.integers(1, 200, n).astype(np.int32),
                    max_new_tokens=new, mct_queries=qs[2 * i:2 * i + 2],
                    connect_minutes=[10 ** 5, 10 ** 5])
            for i, (n, new) in enumerate([(2, 3), (5, 2), (9, 2)])]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        outs = srv.generate_batch(reqs)
    assert [len(c.tokens) for c in outs] == [3, 2, 2]
    args = _span_args(str(tmp_path))
    assert args[LM_SPANS["filter"]] == [{"checks": 6, "rows": 3}]
    assert args[LM_SPANS["prefill"]] == [{"rows": 3}]
    assert args[LM_SPANS["generate"]] == [{"rows": 3}]
    # the batch's padded rows and steps travel on its completions: nine
    # prompt steps and two generation steps, the second only the first
    # row needs
    assert all(c.rows_padded == 4 and c.steps == 11 for c in outs)
    # the phases run in order, filter before prefill before generate
    tr = trace_reduce.load(str(tmp_path))
    (f,), (p,), (g,) = (trace_reduce.host_spans(tr, LM_SPANS[k])
                        for k in ("filter", "prefill", "generate"))
    assert f[1] <= p[0] and p[1] <= g[0]
