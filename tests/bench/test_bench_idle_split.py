"""The readers of the MCT host executor's spans: the device's idle time
split by what the executor was doing, and the queue wait."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny_bench  # noqa: E402

from bench import harness, idle_split  # noqa: E402

IDLE = ("idle.compile_share", "idle.encode_share", "idle.no_batch_share")


def _read(metric, run):
    return harness.load_reader(harness.BENCH_DIR, metric)(run)


def _trace():
    # window 0-100; the device runs 80-90. Worker spans overlap: a compile
    # (20-30) inside one batch, an encode (25-40) inside another.
    return {"window_ns": [0, 100],
            "devices": [{"modules": [["jit_match_rules(1)", 80, 10]],
                         "ops": []}],
            "host": [["mct.execute", -10, 15],
                     ["mct.execute", 10, 50], ["mct.compile", 20, 10],
                     ["mct.device_execute", 40, 20],
                     ["mct.execute", 15, 30], ["mct.encode", 25, 15],
                     ["LSRAv2_core_allocation", 60, 10],
                     ["mct.execute", 70, 25],
                     ["mct.device_execute", 75, 20],
                     ["bench.window", 0, 100, {}]]}


def test_idle_parts_take_their_priority_and_sum_to_the_idle_share():
    tr = _trace()
    parts = idle_split.shares(tr)
    # compile 20-30 (over the encode 25-30); encode 30-40; some other
    # span 0-5, 10-20, 40-60, 70-80, 90-95; nothing open 5-10, 60-70,
    # 95-100 (a compiler's own span is not the executor's)
    assert parts == pytest.approx(
        {"compile": 10.0, "encode": 10.0, "stage": 50.0, "no_batch": 20.0})
    run = SimpleNamespace(trace=tr)
    idle = _read("device.idle_share", run)
    assert idle == pytest.approx(90.0)
    assert sum(parts.values()) == pytest.approx(idle)
    assert [_read(m, run) for m in IDLE] == pytest.approx([10.0, 10.0, 20.0])


@pytest.mark.parametrize("host,parts", [
    # a batch open at the trace's start kept its device_execute and its
    # collect (held 0-21); one open at its end kept its encode and its
    # dispatch (held 90-100); one whole batch 40-50
    ([["mct.device_execute", 2, 18], ["mct.collect", 20, 1],
      ["mct.execute", 40, 10], ["mct.encode", 40, 2],
      ["mct.encode", 90, 3], ["mct.dispatch", 93, 1]],
     {"compile": 0.0, "encode": 5.0, "stage": 36.0, "no_batch": 59.0}),
    # a batch open at both ends kept only its compile
    ([["mct.compile", 30, 30]],
     {"compile": 30.0, "encode": 0.0, "stage": 70.0, "no_batch": 0.0}),
])
def test_batches_cut_by_the_trace_still_hold_the_executor(host, parts):
    tr = {"window_ns": [0, 100], "devices": [{"modules": [], "ops": []}],
          "host": host}
    assert idle_split.shares(tr) == pytest.approx(parts)


@pytest.mark.parametrize("change", ["no_device", "no_executor_span"])
def test_idle_parts_need_a_device_and_the_executor_spans(change):
    tr = _trace()
    if change == "no_device":
        tr["devices"] = []
    else:
        tr["host"] = [e for e in tr["host"] if not e[0].startswith("mct.")]
    run = SimpleNamespace(trace=tr, answers={1: SimpleNamespace(
        times=SimpleNamespace(queue_us=2000.0))})
    assert [_read(m, run) for m in IDLE] == [None] * 3
    wait = _read("executor.queue_wait_ms", run)
    assert wait == (2.0 if change == "no_device" else None)


def test_wrapper_cell_traces_the_executor_stages(tmp_path, monkeypatch):
    tiny_bench.make_tree(tmp_path)
    v5e = harness.peaks_for(harness.BENCH_DIR, "TPU v5 lite")
    monkeypatch.setattr(harness.Run, "peaks", lambda self: v5e)
    # a match compile takes seconds on the CPU, longer than the traced
    # half of the tiny window, and a span cut by the trace is not
    # recorded: one check per query and a first run leave the traced run
    # one compiled count, and a pool it cannot finish
    mix = tiny_bench.tiny_closed_mix()
    one = {"pool": 4000,
           "user_query": dict(mix["user_query"], max_checks=1)}
    tiny_bench.run(tmp_path, cell=tiny_bench.MCT_CELL, mix_override=one)
    runs = []
    res = tiny_bench.run(tmp_path, cell=tiny_bench.MCT_CELL, trace=True,
                         runs_out=runs, mix_override=one)
    assert res["correct"], res["checks"]
    (run,) = runs
    names = {e[0] for e in run.trace["host"]}
    for stage in ("execute", "encode", "dispatch", "device_execute",
                  "collect"):
        assert "mct." + stage in names
    m = res["metrics"]
    waits = sorted(r.times.queue_us / 1e3 for r in run.answers.values())
    assert waits[0] <= m["executor.queue_wait_ms"]["value"] <= waits[-1]
    assert m["executor.queue_wait_ms"]["unit"] == "ms"
    # no TPU here: the device-trace readers find nothing and are left out
    assert not set(IDLE) & set(m)
