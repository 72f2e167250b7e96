"""The comparison that decides ``correct``, at CPU size: the program
passes, the control fails through the same comparison, and each fault
planted in the timed path underneath a run turns ``correct`` false."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny_bench  # noqa: E402


@pytest.fixture
def tree(tmp_path, monkeypatch):
    tiny_bench.reduced_configs(monkeypatch)
    tiny_bench.make_tree(tmp_path)
    return tmp_path


def test_program_passes_and_the_float8_control_fails(tree):
    res = tiny_bench.run(tree, control=True)
    c = res["checks"]
    assert res["correct"], c
    assert c["mct_checks_compared"]["value"] > 0
    assert c["mct_mismatches"]["value"] == 0
    assert c["logit_gap"]["value"] <= c["logit_gap"]["limit"]
    ctrl = res["control"]
    assert not ctrl["correct"], ctrl
    assert ctrl["checks"]["logit_gap"]["value"] > c["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", [tiny_bench.MCT_CELL,
                                  tiny_bench.MCT_OPEN_CELL])
def test_the_wrapper_passes(tree, cell):
    res = tiny_bench.run(tree, cell=cell)
    c = res["checks"]
    assert res["correct"], c
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert c["mct_checks_compared"]["value"] > 0


def test_first_match_control_fails_at_paper_scale():
    """The control answers the checks of two user queries against
    160,000 rules with the first matching rule; some of those checks
    match a more precise rule later in the table, so the comparison that
    passes the best-weight answers fails the control."""
    import json
    from bench import harness, mct, traffic
    mix = json.loads((tiny_bench.ROOT / "bench/traffic/de_closed.json")
                     .read_text())
    mix["pool"] = 2
    rules = mct.generate_rules(160_000, np.random.default_rng(
        [tiny_bench.SEED, 0]))
    tr = traffic.build(mix, 1.0, tiny_bench.SEED, 2, rules)
    run = harness.Run(cell="paper-scale", seed=tiny_bench.SEED, cfg={},
                      mix=mix, seconds=1.0, bench_dir=harness.BENCH_DIR)
    run.traffic = tr
    for q in tr.queries:
        run.due[q.uid], run.query_of[q.uid] = 0.0, q.uid
        run.answers[q.uid] = None
    ctrl = harness.check_answers(run, rules,
                                 harness.control_answers(run, rules))
    assert not harness.is_correct(ctrl), ctrl
    assert ctrl["mct_mismatches"]["value"] > 0
    best, carried, _ = mct.dense_pass(rules, tr.vals)
    dec = np.where(best >= 0, np.asarray(mct.DECISIONS)[carried.argmax(1)],
                   -1)
    right = {q.uid: (dec[q.first_check:q.first_check + q.n_checks],
                     best[q.first_check:q.first_check + q.n_checks])
             for q in tr.queries}
    assert harness.is_correct(harness.check_answers(run, rules, right))


def _token_altered(monkeypatch):
    from repro.serve.engine import LMServer
    real = LMServer.execute_prepared

    def execute(self, pb, *, device=None):
        out = real(self, pb, device=device)
        for c in out:
            c.tokens = np.asarray(c.tokens).copy()
            c.tokens[-1] = (int(c.tokens[-1]) + 1) % self.cfg.vocab
        return out
    monkeypatch.setattr(LMServer, "execute_prepared", execute)


def _decision_altered(monkeypatch):
    from repro.core.engine import ErbiumEngine
    real = ErbiumEngine.match

    def match(self, encoded, device=None):
        dec, w, rid = real(self, encoded, device=device)
        return dec.at[0].add(5), w, rid
    monkeypatch.setattr(ErbiumEngine, "match", match)


def _half_batch_left_out(monkeypatch):
    from repro.serve.engine import LMServer
    real = LMServer.execute_prepared

    def execute(self, pb, *, device=None):
        out = real(self, pb, device=device)
        return out[:len(out) // 2] if len(out) > 1 else out
    monkeypatch.setattr(LMServer, "execute_prepared", execute)


@pytest.mark.parametrize("fault,caught_by", [
    (_token_altered, "logit_gap"), (_decision_altered, "mct_mismatches"),
    (_half_batch_left_out, "verdict_mismatches")])
def test_a_fault_in_the_timed_path_is_not_correct(tree, monkeypatch,
                                                  fault, caught_by):
    fault(monkeypatch)
    res = tiny_bench.run(tree)
    c = res["checks"][caught_by]
    assert not res["correct"], res["checks"]
    assert c["value"] > c["limit"], res["checks"]


def _answer_altered(monkeypatch):
    from repro.core.wrapper import MCTWrapper
    real = MCTWrapper._execute

    def execute(self, t_in, batch, eng_idx):
        res = real(self, t_in, batch, eng_idx)
        res.decisions = np.asarray(res.decisions).copy()
        res.decisions[-1] += 5
        return res
    monkeypatch.setattr(MCTWrapper, "_execute", execute)


def _half_of_the_answers_left_out(monkeypatch):
    from repro.core.wrapper import MCTWrapper
    real = MCTWrapper._execute

    def execute(self, t_in, batch, eng_idx):
        res = real(self, t_in, batch, eng_idx)
        k = len(res.decisions) // 2
        res.decisions, res.weights = res.decisions[k:], res.weights[k:]
        return res
    monkeypatch.setattr(MCTWrapper, "_execute", execute)


@pytest.mark.parametrize("fault,caught_by", [
    (_answer_altered, "mct_mismatches"),
    (_half_of_the_answers_left_out, "answers_missing")])
def test_a_fault_in_the_wrapper_is_not_correct(tree, monkeypatch, fault,
                                               caught_by):
    fault(monkeypatch)
    res = tiny_bench.run(tree, cell=tiny_bench.MCT_CELL)
    c = res["checks"][caught_by]
    assert not res["correct"], res["checks"]
    assert c["value"] > c["limit"], res["checks"]
