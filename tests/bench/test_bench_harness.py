"""The benchmark's yardstick on the CPU: counts, the trace reduction, the
peaks table, and finding configurations, mixes and metrics by name."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny_bench  # noqa: E402

from bench import flops, harness, lm_ref, mct, trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _cfg(name):
    return harness.load_config(harness.BENCH_DIR, name)


@pytest.mark.parametrize("name,params", [("hymba-1.5b", 1_641_784_000),
                                         ("internlm2-20b", 5_818_177_536)])
def test_parameter_counts(name, params):
    assert flops.n_params(_cfg(name)) == params


@pytest.mark.parametrize("name", ["hymba-1.5b", "internlm2-20b"])
def test_weights_are_laid_out_as_the_program_takes_them(name):
    import jax
    from bench import program
    from repro.models.registry import build_model
    cfg = _cfg(name)
    prog = jax.eval_shape(build_model(program.model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: lm_ref.init_params(cfg, 0))
    leaf = lambda x: (x.shape, str(x.dtype))  # noqa: E731
    assert jax.tree_util.tree_map(leaf, prog) == \
        jax.tree_util.tree_map(leaf, ours)


def test_table_bytes_at_paper_scale():
    assert flops.table_bytes(160_256, 31) == 40_384_512
    assert flops.match_bytes(100, 160_256, 31) == 40_384_512 + 100 * (31 + 3) * 4


def test_lm_flops_counts_windows_and_state():
    cfg = _cfg("hymba-1.5b")
    mats = 2 * (cfg["n_layers"] * sum(
        s[0] * s[1] for s, _, k in lm_ref.leaf_shapes(cfg).values()
        if k == "dense") + cfg["vocab"] * cfg["d_model"])
    # position 0 sees one position in every layer
    base = flops.lm_flops(cfg, 0)
    assert base > mats
    # past the 1,024 window only the 3 global layers keep growing
    step = flops.lm_flops(cfg, 2000) - flops.lm_flops(cfg, 1999)
    assert step == 3 * 4 * cfg["n_heads"] * cfg["head_dim"]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for(harness.BENCH_DIR,
                             "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for(harness.BENCH_DIR, "cpu")


def test_reference_matcher_agrees_with_a_loop():
    import numpy as np
    rng = np.random.default_rng(3)
    rules = mct.generate_rules(300, rng)
    vals = mct.effective_values(mct.generate_checks(rules, 64, rng))
    best = mct.best_weights(rules, vals)
    for i in range(len(vals)):
        ok = mct.rule_matches(rules, np.arange(rules.n),
                              np.broadcast_to(vals[i], (rules.n, 26)))
        assert best[i] == (rules.weight[ok].max() if ok.any() else -1)
    assert (best >= 0).mean() > 0.5             # most checks match a rule


def _recorded_trace():
    return json.loads((DATA / "trace_small.json").read_text())


def test_trace_reduction_on_a_recorded_trace():
    tr = _recorded_trace()
    w = trace_reduce.window_s(tr)
    busy = trace_reduce.busy_s(tr)
    assert 0 < busy < w
    ops = trace_reduce.top_ops(tr, 10)
    assert len(ops) <= 10 and ops == sorted(ops, key=lambda o: -o[1])
    assert sum(t for _, t in ops) <= busy * 1.0001 + 1e-9 or len(ops) == 10
    gaps = trace_reduce.idle_gaps(tr, 10)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    idle = sum(g[1] for g in trace_reduce.idle_gaps(tr, 10**6))
    assert abs(idle + busy - w) < 1e-6


def test_union_and_labels():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    tr = {"window_ns": [0, 100], "host": [
        ["bench.execute_prepared", 0, 100], ["compile", 10, 40]],
        "devices": [{"modules": [["jit_f(1)", 60, 40]], "ops": []}]}
    assert trace_reduce.busy_s(tr) == 40e-9
    assert trace_reduce.idle_gaps(tr) == [["compile", 60e-9]]
    assert trace_reduce.module_runs(tr, "jit_f") == [(60, 100)]


def test_harness_finds_added_files_by_name(tmp_path, monkeypatch):
    """A new configuration, traffic mix and per-layer metric are picked up
    from added files alone; every file that was there is unchanged."""
    tiny_bench.reduced_configs(monkeypatch)
    bench = tiny_bench.make_tree(tmp_path)
    (bench / "metrics" / "tiny.batches.py").write_text(
        "def read(run):\n    return len(run.report.batch_sizes)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "tiny.batches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "admission and batching",
        "moves": "uq_p95_ms", "workloads": [tiny_bench.CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for f in (tiny_bench.ROOT / "bench").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            assert (bench / f.relative_to(tiny_bench.ROOT / "bench")
                    ).read_bytes() == f.read_bytes()
    # the CPU has no entry in the peaks table; borrow the v5e's
    v5e = harness.peaks_for(harness.BENCH_DIR, "TPU v5 lite")
    monkeypatch.setattr(harness.Run, "peaks", lambda self: v5e)
    res = tiny_bench.run(tmp_path, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tiny.batches"]["value"] >= 1
    assert res["metrics"]["window.compiles"]["value"] >= 1
    assert res["metrics"]["encode.us_per_check"]["unit"] == "us/check"
    # no TPU here: the device-trace metrics find nothing and are left out
    assert "rule_match_roofline" not in res["metrics"]
    assert [k for k in res if k != "_log"][-1] == "checks"


def test_device_trace_readers_on_the_recorded_trace():
    from types import SimpleNamespace
    v5e = harness.peaks_for(harness.BENCH_DIR, "TPU v5 lite")
    run = SimpleNamespace(
        trace=_recorded_trace(), table_shape=(31, 160_256),
        modules={"match": "jit_match_rules", "decode": "jit__lambda"},
        peaks=lambda: v5e)
    read = lambda m: harness.load_reader(harness.BENCH_DIR, m)(run)  # noqa
    roof = read("rule_match_roofline")
    assert 0 < roof < 100
    # two match programs ran in the slice; the first was launched before
    # it (no span to pair with), the second by the span of 711 checks
    runs = trace_reduce.module_runs(run.trace, "jit_match_rules")
    assert len(runs) == 2
    s, e = runs[1]
    least = flops.match_bytes(711, 160_256, 31) / 819e9
    assert roof == pytest.approx(100 * least / ((e - s) / 1e9))
    assert 0 < read("lm.step_ms") < 100
    assert 0 < read("device.idle_share") < 100


def test_closed_loop_pool_gives_every_seed_the_same_sizes_per_block():
    mix = harness.load_mix(harness.BENCH_DIR, "de_closed")
    mix = dict(mix, pool=3 * mix["clients"])
    mix["user_query"] = dict(mix["user_query"], ts_mean=20, max_checks=40)
    rules = mct.generate_rules(500, __import__("numpy").random
                               .default_rng(0))
    from bench import traffic
    a, b = (traffic.build(mix, 1.0, s, 2, rules) for s in (1, 2**31 + 5))
    c = mix["clients"]
    for k in range(0, mix["pool"], c):
        sizes = [sorted(q.n_checks for q in t.queries[k:k + c])
                 for t in (a, b)]
        assert sizes[0] == sizes[1]
    assert [q.n_checks for q in a.queries] != [q.n_checks for q in b.queries]


def test_wrapper_cell_reports_its_metrics(tmp_path, monkeypatch):
    tiny_bench.make_tree(tmp_path)
    v5e = harness.peaks_for(harness.BENCH_DIR, "TPU v5 lite")
    monkeypatch.setattr(harness.Run, "peaks", lambda self: v5e)
    res = tiny_bench.run(tmp_path, cell=tiny_bench.MCT_CELL, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["window.compiles"]["value"] >= 1
    assert m["encode.us_per_check"]["value"] > 0
    assert 0 <= m["window.repeat_share"]["value"] < 100
    # no TPU here: the device-trace metrics find nothing and are left out
    assert "rule_match_roofline" not in m
    e2e = tiny_bench.run(tmp_path, cell=tiny_bench.MCT_CELL)["metrics"]
    assert set(e2e) == {"uq_per_s", "setup_s"}
    assert e2e["uq_per_s"]["value"] > 0
