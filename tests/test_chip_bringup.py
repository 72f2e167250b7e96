"""What keeps the main path honest about its device, checked on the CPU:
``chip_smoke.py`` refuses a backend that is not a TPU, the rule matcher
picks interpret mode from the backend (no option), and the compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to the repo root."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.core.compiler import compile_rules
from repro.core.encoder import encode_queries
from repro.core.engine import ErbiumEngine, cpu_match_numpy
from repro.core.rules import generate_queries, generate_rules
from repro.kernels import ops
from repro.kernels import rule_match

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_device_phase_refuses_cpu(capsys):
    assert jax.default_backend() == "cpu"
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().phase_device()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


@pytest.fixture
def interpret_calls(monkeypatch):
    """Records the ``interpret`` value every pallas_call is traced with."""
    calls = []
    real = rule_match.pl.pallas_call

    def spy(*args, **kw):
        calls.append(kw.get("interpret"))
        return real(*args, **kw)

    monkeypatch.setattr(rule_match.pl, "pallas_call", spy)
    return calls


def test_matcher_interprets_on_cpu_by_default(interpret_calls):
    rs = generate_rules(200, version=2, seed=21)
    table = compile_rules(rs)
    enc = encode_queries(table, generate_queries(rs, 48, seed=22))
    want = cpu_match_numpy(table, enc)
    # tile sizes no other test uses, so both calls trace afresh
    eng = ErbiumEngine(table, tile_b=24, tile_r=40)
    for got, w in zip(eng.match(enc), want):
        np.testing.assert_array_equal(np.asarray(got), w)
    direct = ops.match_rules(jax.numpy.asarray(enc), eng.dt, tile_b=40,
                             tile_r=40)
    for got, w in zip(direct, want):
        np.testing.assert_array_equal(np.asarray(got), w)
    assert interpret_calls == [True, True]


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
