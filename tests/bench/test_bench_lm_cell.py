"""The model cell's path on the CPU: the tiny hybrid cell served with
mixed prompt lengths in a batch is correct and its float8 control is
not, and the LM readers (``lm.prefill_ms``, ``lm.generate_ms``,
``lm.pad_share``) read what the program's spans and completions hold."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny_bench  # noqa: E402

from bench import harness  # noqa: E402

LM_READERS = ("lm.prefill_ms", "lm.generate_ms", "lm.pad_share",
              "lm.step_ms", "admission.queue_wait_ms",
              "idle.compile_share")


def _read(metric, run):
    return harness.load_reader(harness.BENCH_DIR, metric)(run)


def test_mixed_lengths_cell_is_correct_and_its_control_is_not(
        tmp_path, monkeypatch):
    tiny_bench.reduced_configs(monkeypatch)
    tiny_bench.make_tree(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        if m["name"] in LM_READERS:
            m["workloads"].append(tiny_bench.CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = tiny_bench.run(tmp_path, trace=True, control=True, seconds=3.0,
                         mix_override={"prompt_tokens": [4, 12]})
    log = res["_log"]
    assert res["correct"], (res["checks"], log["worst_token"])
    assert res["checks"]["lm_tokens_compared"]["value"] >= 8
    assert not res["control"]["correct"], res["control"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["lm.prefill_ms"] > 0 and m["lm.generate_ms"] > 0
    assert 0 <= m["lm.pad_share"] < 100
    assert m["admission.queue_wait_ms"] >= 0
    # the CPU's trace holds no device plane: the device readers are silent
    assert "lm.step_ms" not in m and "idle.compile_share" not in m


def _trace(host):
    return {"window_ns": [1_000, 9_000], "devices": [], "host": host}


def test_phase_time_readers_take_the_median_inside_the_window():
    run = SimpleNamespace(trace=_trace([
        ["lm.prefill", 500, 2_000_000],          # starts before the window
        ["lm.prefill", 1_000, 3_000],
        ["lm.prefill", 2_000, 1_000],
        ["lm.prefill", 5_000, 2_000],
        ["lm.generate", 4_000, 4_000],
        ["bench.decode_step", 4_100, 100, {}]]))
    assert _read("lm.prefill_ms", run) == pytest.approx(2_000 / 1e6)
    assert _read("lm.generate_ms", run) == pytest.approx(4_000 / 1e6)
    # a trace without the program's spans, and an untraced run
    empty = SimpleNamespace(trace=_trace([["bench.window", 1_000, 8_000]]))
    for metric in ("lm.prefill_ms", "lm.generate_ms"):
        assert _read(metric, empty) is None
        assert _read(metric, SimpleNamespace(trace=None)) is None


def test_pad_share_counts_pad_rows_and_pad_steps():
    """The batch of ``test_lm_spans.py``: prompts of 2, 5 and 9 tokens in
    a bucket of four rows, 3, 2 and 2 new tokens, 11 steps: 16 + 4 real
    tokens over 4 x 9 + 4 x 2 slots."""
    prompts = [np.ones(n, np.int32) for n in (2, 5, 9)]
    comps = {rid: SimpleNamespace(tokens=np.zeros(new, np.int32),
                                  batch_size=3, rows_padded=4, steps=11)
             for rid, new in enumerate((3, 2, 2))}
    run = SimpleNamespace(
        completions=comps, t0=0.0, t1=10.0,
        verdict={rid: (1.0, "scored") for rid in comps},
        query_of={rid: rid for rid in comps},
        traffic=SimpleNamespace(queries=[SimpleNamespace(prompt=p)
                                         for p in prompts]))
    assert _read("lm.pad_share", run) == pytest.approx(100 * (1 - 20 / 44))
    # a completion answered after the window is not counted
    run.verdict[2] = (11.0, "scored")
    assert _read("lm.pad_share", run) == pytest.approx(
        100 * (1 - 10 / (2 * 44 / 3)))
    # completions without the counts (a program that does not keep them)
    for c in comps.values():
        del c.steps
    assert _read("lm.pad_share", run) is None
