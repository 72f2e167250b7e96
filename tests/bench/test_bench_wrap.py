"""The MCT wrapper's closed loop wraps round its pool: a program that
answers the whole pool inside the window is still offered work until the
window closes, every repeated answer is compared, and the reference runs
once per distinct check."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny_bench  # noqa: E402

# below the four clients of the tiny closed mix, so the first
# submissions already wrap
SMALL_POOL = 2


@pytest.fixture
def tree(tmp_path):
    tiny_bench.make_tree(tmp_path)
    return tmp_path


def _repeat_share(run):
    from bench import harness
    return harness.load_reader(harness.BENCH_DIR, "window.repeat_share")(run)


def _run(tree, pool, **kw):
    runs = []
    res = tiny_bench.run(tree, cell=tiny_bench.MCT_CELL,
                         mix_override={"pool": pool}, runs_out=runs, **kw)
    return res, runs[0]


def test_a_wrapped_pool_is_compared_in_full_and_checked_once(
        tree, monkeypatch):
    from bench import mct
    seen = []
    dense = mct.dense_pass

    def counted(rules, vals, *a, **kw):
        seen.append(len(vals))
        return dense(rules, vals, *a, **kw)
    monkeypatch.setattr(mct, "dense_pass", counted)

    res, run = _run(tree, SMALL_POOL)
    log, c = res["_log"], res["checks"]
    assert res["correct"], c
    assert log["laps"] >= 1
    assert log["submissions"] == len(run.due) > SMALL_POOL
    assert set(run.answers) == set(run.due)
    assert len(set(run.due)) == len(run.due)
    qs = run.traffic.queries
    assert c["mct_checks_compared"]["value"] == sum(
        qs[run.query_of[r]].n_checks for r in run.answers)
    distinct = {run.query_of[r] for r in run.answers}
    assert seen == [sum(qs[i].n_checks for i in distinct)] \
        == [log["checks_distinct"]]
    assert seen[0] < c["mct_checks_compared"]["value"]
    share = _repeat_share(run)
    assert share > 0
    assert share == pytest.approx(100 * (1 - len(distinct) / len(run.due)))


def test_a_fault_in_a_second_lap_answer_is_not_correct(tree, monkeypatch):
    """Later laps carry ids past the pool's uids; one weight of each
    second-lap answer is altered, the first lap is left sound."""
    from repro.core.wrapper import MCTWrapper
    real = MCTWrapper._execute

    def execute(self, t_in, batch, eng_idx):
        res = real(self, t_in, batch, eng_idx)
        if SMALL_POOL <= batch.uid < 2 * SMALL_POOL:
            res.weights = np.asarray(res.weights).copy()
            res.weights[0] += 1
        return res
    monkeypatch.setattr(MCTWrapper, "_execute", execute)

    res, run = _run(tree, SMALL_POOL)
    c = res["checks"]["mct_mismatches"]
    assert res["_log"]["laps"] >= 1
    assert not res["correct"], res["checks"]
    second = [r for r in run.answers
              if SMALL_POOL <= r < 2 * SMALL_POOL]
    assert c["value"] == len(second) > 0


def test_a_pool_that_lasts_the_window_does_not_wrap(tree):
    res, run = _run(tree, 200)
    assert res["correct"], res["checks"]
    assert res["_log"]["laps"] == 0
    assert res["_log"]["submissions"] == len(run.due) < 200
    uids = {q.uid for q in run.traffic.queries}
    assert set(run.due) <= uids
    assert all(run.query_of[r] == r for r in run.due)
    assert _repeat_share(run) == 0
