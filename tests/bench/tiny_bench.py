"""A benchmark tree at CPU size for the tests: a copy of ``bench/`` with
a tiny hybrid configuration (the program's reduced Hymba) under a short
Domain Explorer mix, and a small rule set behind the MCT wrapper under a
short closed loop, added as files, and a ``BENCHMARK.json`` naming both
tiny cells beside the real ones."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny-hybrid.tiny_de"
MCT_CELL = "tiny-mct.tiny_de_closed"
MCT_OPEN_CELL = "tiny-mct.tiny_de_open"
REAL_MCT_CELL = "mct-v2-160k.de_closed"
# widest served-token logit gap of the tiny bfloat16 model against the
# float32 reference (CPU, seeds 1-8, 24 tokens each): sound runs read
# 0 to 0.051; the float8 control 0.18 to 0.43 on seven seeds and 0 on
# seed 2, where its choices agreed at every compared position. The
# limit sits between; the tests use seed 1 (0.008 against 0.31).
TINY_GAP_LIMIT = 0.1
SEED = 1


def reduced_configs(monkeypatch) -> None:
    """Serve the program's reduced (CPU-sized) model configurations."""
    import repro.configs.base as base
    full = base.get_config
    monkeypatch.setattr(base, "get_config", lambda a: full(a).reduced())


def tiny_config() -> dict:
    from repro.configs.base import get_config
    r = get_config("hymba-1.5b").reduced()
    return {
        "name": "tiny-hybrid", "model": "hymba-1.5b",
        "n_layers": r.n_layers, "d_model": r.d_model, "n_heads": r.n_heads,
        "n_kv_heads": r.n_kv_heads, "head_dim": r.head_dim, "d_ff": r.d_ff,
        "vocab": r.vocab, "rope_theta": r.rope_theta,
        "norm_eps": r.norm_eps, "attn_pattern": list(r.attn_pattern),
        "parallel_ssm": True,
        "ssm": {"state_dim": r.ssm.state_dim,
                "d_inner_mult": r.ssm.d_inner_mult,
                "conv_width": r.ssm.conv_width},
        "rules": {"n_rules": 2000},
        "serve": {"target_batch": 4, "deadline_s": 0.02,
                  "pipeline_depth": 2, "max_queue": 256, "max_seq": 32},
        "logit_gap_limit": TINY_GAP_LIMIT}


def tiny_mix() -> dict:
    """The DE mix, shortened, at one prompt length: the serving stack
    serves a batch of mixed prompt lengths wrongly (PERF.md, open
    questions), and these tests are of the harness, not of that fault."""
    mix = json.loads((ROOT / "bench/traffic/de_steady.json").read_text())
    mix.update(rate_per_s=12.0, prompt_tokens=[8, 8], check_tokens=24)
    mix["user_query"] = dict(mix["user_query"], ts_mean=20, max_checks=40)
    return mix


def tiny_mct_config() -> dict:
    cfg = json.loads((ROOT / "bench/configs/mct-v2-160k.json").read_text())
    cfg.update(name="tiny-mct", rules=dict(cfg["rules"], n_rules=2000),
               wrapper={"n_workers": 2})
    return cfg


def tiny_closed_mix() -> dict:
    mix = json.loads((ROOT / "bench/traffic/de_closed.json").read_text())
    mix.update(clients=4, pool=40)
    mix["user_query"] = dict(mix["user_query"], ts_mean=20, max_checks=40)
    return mix


# the serving stack's metrics, for the tiny LM cell (no cell of
# BENCHMARK.json serves a model yet)
_LM_METRICS = {
    "end_to_end": [
        {"name": "uq_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "uq_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"}],
    "per_layer": [
        {"name": "admission.queue_wait_ms", "unit": "ms",
         "better": "lower", "source": "program_counter",
         "layer": "admission and batching", "moves": "uq_p95_ms"}]}
_LM_SHARED = ("window.compiles", "encode.us_per_check",
              "rule_match_roofline")


def make_tree(tmp: Path) -> Path:
    """``tmp`` as a checkout root: BENCHMARK.json plus ``bench/``."""
    bench = tmp / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, obj in (("configs/tiny-hybrid", tiny_config()),
                      ("traffic/tiny_de", tiny_mix()),
                      ("configs/tiny-mct", tiny_mct_config()),
                      ("traffic/tiny_de_closed", tiny_closed_mix()),
                      ("traffic/tiny_de_open", dict(
                          tiny_closed_mix(), loop="open", rate_per_s=8.0))):
        (bench / f"{name}.json").write_text(json.dumps(obj))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] += [
        {"name": CELL, "config": "tiny-hybrid", "traffic": "tiny_de",
         "chips": 1, "why": "CPU-sized DE cell through the serving stack"},
        {"name": MCT_CELL, "config": "tiny-mct", "traffic": "tiny_de_closed",
         "chips": 1, "why": "CPU-sized copy of the MCT wrapper cell"},
        {"name": MCT_OPEN_CELL, "config": "tiny-mct",
         "traffic": "tiny_de_open", "chips": 1,
         "why": "the MCT wrapper under an open loop"}]
    for group in ("end_to_end", "per_layer"):
        spec[group] += [dict(m, workloads=[CELL])
                        for m in _LM_METRICS[group]]
        for m in spec[group]:
            if REAL_MCT_CELL in m.get("workloads", []):
                m["workloads"].append(MCT_CELL)
            if m["name"] in _LM_SHARED:
                m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def run(tmp: Path, seed: int = SEED, *, trace: bool = False,
        control: bool = False, cell: str = CELL, seconds: float = 1.5,
        **kw) -> dict:
    import time
    from bench import harness
    return harness.run_cell(tmp, cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            bench_dir=tmp / "bench", control=control, **kw)
