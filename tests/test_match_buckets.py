"""ErbiumEngine.match pads its host rows to the kernel's batch tile, so the
jitted match compiles once per bucket of that tile, not once per row
count, and cuts the answers back to one per row on the host: slicing a
device array would compile once per row count again."""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.compiler import compile_rules
from repro.core.encoder import encode_queries
from repro.core.engine import ErbiumEngine, cpu_match_numpy
from repro.core.rules import generate_queries, generate_rules

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.harness import _CompileCounter  # noqa: E402


@pytest.fixture(scope="module")
def rules():
    rs = generate_rules(200, version=2, seed=31)
    table = compile_rules(rs)
    enc = encode_queries(table, generate_queries(rs, 130, seed=32))
    return table, enc, cpu_match_numpy(table, enc)


def test_one_compile_per_bucket(rules):
    table, enc, want = rules
    # a tile_b and tile_r no other test uses, so every bucket is new here
    eng = ErbiumEngine(table, tile_b=40, tile_r=64, backend="ref")
    for counts, compiles in ((range(1, 121, 2), 3), (range(2, 121, 2), 0)):
        # the backend-compile counter that window.compiles reads
        counter = _CompileCounter()
        counter.on = True
        try:
            for n in counts:
                for got, w in zip(eng.match(enc[:n]), want):
                    np.testing.assert_array_equal(got, w[:n])
        finally:
            counter.close()
        assert counter.n == compiles


@pytest.mark.parametrize("n,kw", [
    (21, dict(tile_b=16, tile_r=64)),                 # pallas, interpreted
    (21, dict(tile_b=8, tile_r=64, n_engines=2)),     # two kernel lanes
    (21, dict(tile_b=16, tile_r=64, backend="ref")),
    (21, dict(tile_b=16, tile_r=64, partitioned=True)),
    (32, dict(tile_b=16, tile_r=64, partitioned=True)),   # a whole bucket
])
def test_padded_rows_leave_the_answers(rules, n, kw):
    table, enc, want = rules
    for got, w in zip(ErbiumEngine(table, **kw).match(enc[:n]), want):
        np.testing.assert_array_equal(got, w[:n])


def test_match_queries_answers_each_query_once():
    rs = generate_rules(120, version=2, seed=33)
    eng = ErbiumEngine(compile_rules(rs), tile_b=16, tile_r=64,
                       backend="ref")
    qs = generate_queries(rs, 19, seed=34)
    got = eng.match_queries(qs)
    want = cpu_match_numpy(eng.table, eng.encode_queries_host(qs))
    for g, w in zip(got, want):
        assert len(g) == len(qs)
        np.testing.assert_array_equal(g, w)
