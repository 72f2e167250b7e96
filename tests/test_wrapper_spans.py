"""The MCT host executor's stage spans on the profiler's clock: every
stage is spanned inside its batch's ``mct.execute``, and a match call is
labelled ``mct.compile`` exactly when it compiled."""
import sys
import threading
from pathlib import Path

import jax
import pytest

from repro.core.aggregator import Batch
from repro.core.compiler import compile_rules
from repro.core.engine import ErbiumEngine
from repro.core.rules import generate_queries, generate_rules
from repro.core.wrapper import MCTWrapper
from repro.serve.trace import EXECUTOR_SPANS

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace_reduce  # noqa: E402

# tile_b is a static argument of the jitted match: one that no other test
# uses keeps this file's compiles its own
TILE_B = 40


def _engine(seed):
    rs = generate_rules(300, version=2, seed=seed)
    return rs, ErbiumEngine(compile_rules(rs), tile_b=TILE_B, tile_r=128,
                            backend="ref")


def _batch(uid, qs, n):
    return Batch(uid, qs[:n], [(uid, j) for j in range(n)])


def test_stage_spans_and_compile_labels(tmp_path):
    rs, e1 = _engine(5)
    _, e2 = _engine(6)
    assert e1.dt.mins_t.shape == e2.dt.mins_t.shape
    qs = generate_queries(rs, 90, seed=1)
    wrap = MCTWrapper([e1, e2], n_workers=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    wrap.start()
    results = []
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            # rows are padded to a multiple of TILE_B: 3 and 45 and 85
            # checks fall in three buckets, the second 3 in the first's
            for uid, n in enumerate([3, 45, 3, 85]):
                wrap.submit(_batch(uid, qs, n))
                results += wrap.drain(1)
            # the second engine's table has the same shape: no compile
            results.append(wrap.process(_batch(9, qs, 3), engine_idx=1))
    finally:
        wrap.stop()
    for r, n in zip(results, [3, 45, 3, 85, 3]):
        assert len(r.decisions) == len(r.weights) == r.times.batch == n
    times = [r.times for r in results]
    tr = trace_reduce.load(str(tmp_path))
    spans = {s: trace_reduce.host_spans(tr, name)
             for s, name in EXECUTOR_SPANS.items()}
    executes = spans["execute"]
    assert len(executes) == 5
    for stage in ("encode", "dispatch", "device_execute", "collect"):
        assert len(spans[stage]) == 5
    for stage, found in spans.items():
        for s, e, _ in found:
            assert any(a <= s and e <= b for a, b, _ in executes), stage
    compiles = trace_reduce.host_spans(tr, "backend_compile_and_load")
    assert len(spans["compile"]) == len(compiles) == 3
    # each compile span holds the backend compile it labels
    for s, e, _ in compiles:
        assert any(a <= s and e <= b for a, b, _ in spans["compile"])
    assert [t.compile_us > 0 for t in times] == [True, True, False, True,
                                                False]
    for t in times:
        assert t.kernel_us > 0 and t.dispatch_us > 0
        assert t.total_us == pytest.approx(
            t.queue_us + t.encode_us + t.dispatch_us + t.compile_us +
            t.kernel_us + t.collect_us)


def test_workers_racing_on_a_new_count_are_both_labelled():
    rs, eng = _engine(7)
    # 130 rows: a bucket (160) no other test here reaches
    enc = eng.encode_queries_host(generate_queries(rs, 130, seed=2))
    start = threading.Barrier(2)
    took = []

    def call():
        start.wait()
        jax.block_until_ready(eng.match(enc))
        took.append(eng.last_compile_s())

    threads = [threading.Thread(target=call) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(took) == 2 and all(s > 0 for s in took)
    jax.block_until_ready(eng.match(enc))
    assert eng.last_compile_s() == 0.0
