"""Mixed prompt lengths in one batch, served through ``LMServer``, against
the plain float32 reference (``bench/lm_ref.py``).

The server left-pads a batch's prompts to a common end and the decode
cache carries each row's start, so a row's lead-in steps stay out of its
KV cache and Mamba state. Every served step's logits are compared with
the reference's forward pass over that row's own tokens.

Tolerance: the server runs in bfloat16 on the same bfloat16 weights the
reference reads in float32, so each logit differs by bfloat16 rounding of
the activations through four layers and the unembedding. The widest
difference read 0.051-0.057 for the hybrid and 0.031-0.033 for the
attention-only config, at logit scales of 3.0-4.1 (CPU): under 2% of the
scale. The limit is 5% of each row's largest reference logit. The old
right-padded serving, where a shorter prompt's tokens followed pad zeros,
reads 3.1-4.5 at the same scales (CPU), and
``test_right_padding_fails_the_tolerance`` keeps that reading.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import ssm as ssm_mod
from repro.models.registry import build_model
from repro.serve.engine import LMServer, Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import lm_ref  # noqa: E402

REL_TOL = 0.05
LENGTHS = (3, 7, 12)           # three rows, padded to a bucket of four
NEW_TOKENS = 5
MAX_SEQ = 32


def _model_config(arch, **changes):
    mc = get_config(arch).reduced()
    # the reference has an unembedding matrix of its own
    return dataclasses.replace(mc, tie_embeddings=False, **changes)


def _ref_config(mc) -> dict:
    """The reference's description of ``mc``."""
    cfg = {"n_layers": mc.n_layers, "d_model": mc.d_model,
           "n_heads": mc.n_heads, "n_kv_heads": mc.n_kv_heads,
           "head_dim": mc.head_dim, "d_ff": mc.d_ff, "vocab": mc.vocab,
           "rope_theta": mc.rope_theta, "norm_eps": mc.norm_eps,
           "attn_pattern": list(mc.attn_pattern),
           "parallel_ssm": mc.parallel_ssm}
    if mc.parallel_ssm:
        cfg["ssm"] = {"state_dim": mc.ssm.state_dim,
                      "d_inner_mult": mc.ssm.d_inner_mult,
                      "conv_width": mc.ssm.conv_width}
    return cfg


def _prompts(vocab, lengths=LENGTHS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]


def _serve_capturing(srv, reqs):
    """Serve ``reqs`` as one batch; also return each decode step's
    last-position logits, keyed by the step's position."""
    steps = {}
    decode = srv._decode

    def capture(params, cache, tokens, pos):
        logits, cache = decode(params, cache, tokens, pos)
        steps[int(pos)] = np.asarray(logits[:, -1], np.float32)
        return logits, cache

    srv._decode = capture
    try:
        outs = srv.generate_batch(reqs)
    finally:
        srv._decode = decode
    return outs, steps


def _worst_relative_gap(params, cfg, prompts, outs, steps):
    """Widest |served - reference| logit over every served step of every
    row, over that row's largest reference logit."""
    max_p = max(len(p) for p in prompts)
    worst = 0.0
    for i, (p, c) in enumerate(zip(prompts, outs)):
        seq = np.concatenate([p, c.tokens[:-1]])[None]
        pos = np.arange(len(p) - 1, seq.shape[1])[None]
        ref = lm_ref.logits_at(params, seq, pos, cfg)[0]
        served = np.stack([steps[max_p - 1 + j][i]
                           for j in range(len(c.tokens))])
        worst = max(worst, float(np.abs(served - ref).max()
                                 / np.abs(ref).max()))
    return worst


@pytest.mark.parametrize("arch,changes", [
    ("hymba-1.5b", {}),
    # a window of 8 that prompt and generation (up to 16 tokens) exceed
    ("hymba-1.5b", {"attn_pattern": (0, 8)}),
    ("llama3.2-3b", {}),
], ids=["hybrid", "hybrid-window-8", "attention-only"])
def test_mixed_lengths_match_the_reference(arch, changes):
    mc = _model_config(arch, **changes)
    cfg = _ref_config(mc)
    params = lm_ref.init_params(cfg, 3)
    srv = LMServer(mc, params=params, max_seq=MAX_SEQ)
    prompts = _prompts(mc.vocab)
    reqs = [Request(rid=i, tokens=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    outs, steps = _serve_capturing(srv, reqs)
    assert [len(c.tokens) for c in outs] == [NEW_TOKENS] * len(prompts)
    assert all(c.rows_padded == 4 for c in outs)
    assert all(c.steps == max(LENGTHS) + NEW_TOKENS - 1 for c in outs)
    assert _worst_relative_gap(params, cfg, prompts, outs, steps) <= REL_TOL
    # the same prompt served alone gives the same tokens
    for r, c in zip(reqs, outs):
        alone = srv.generate_batch([r])[0]
        np.testing.assert_array_equal(alone.tokens, c.tokens)


def test_right_padding_fails_the_tolerance():
    """The serving this file replaced: prompts right-padded with token 0
    and every row started at position 0, so a shorter prompt's last
    tokens follow pad zeros in its cache and state. The longest row is
    still right; the others miss the tolerance many times over."""
    mc = _model_config("hymba-1.5b")
    cfg = _ref_config(mc)
    params = lm_ref.init_params(cfg, 3)
    model = build_model(mc)
    step = jax.jit(model.decode_step)
    prompts = _prompts(mc.vocab)
    max_p = max(LENGTHS)
    toks = np.zeros((4, max_p), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    cache = model.init_cache(4, MAX_SEQ)
    for pos in range(max_p):
        logits, cache = step(params, cache, jnp.asarray(toks[:, pos:pos + 1]),
                             jnp.int32(pos))
    served = np.asarray(logits[:, -1], np.float32)
    rel = []
    for i, p in enumerate(prompts):
        ref = lm_ref.logits_at(params, p[None], np.asarray([[len(p) - 1]]),
                               cfg)[0, 0]
        rel.append(float(np.abs(served[i] - ref).max() / np.abs(ref).max()))
    assert rel[-1] <= REL_TOL
    assert min(rel[:-1]) > 10 * REL_TOL, rel


def test_pad_step_leaves_the_mamba_state_bit_identical():
    mc = get_config("hymba-1.5b").reduced()
    p = ssm_mod.init_mamba(jax.random.PRNGKey(0), mc.d_model, mc.ssm,
                           jnp.float32)
    W, di = p["conv_w"].shape
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    st = ssm_mod.MambaState(
        conv=jax.random.normal(k[0], (2, W - 1, di)),
        h=jax.random.normal(k[1], (2, di, mc.ssm.state_dim)))
    x = jax.random.normal(k[2], (2, 1, mc.d_model))
    _, free = ssm_mod.mamba_step(p, x, st, cfg=mc.ssm)
    _, kept = ssm_mod.mamba_step(p, x, st, cfg=mc.ssm,
                                 live=jnp.asarray([False, True]))
    for got, was, new in zip(kept, st, free):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(was[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(new[1]))
        assert not np.array_equal(np.asarray(new[0]), np.asarray(was[0]))


def test_pad_steps_leave_the_decode_cache_state_unchanged():
    """Through the whole decode step: a row before its start keeps its
    Mamba conv buffer and state exactly as they were."""
    mc = get_config("hymba-1.5b").reduced()
    model = build_model(mc)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(2, 8, start=np.asarray([2, 0]))
    step = jax.jit(model.decode_step)
    tok = jnp.asarray([[5], [6]], jnp.int32)
    for pos in range(3):
        before = cache
        _, cache = step(params, jax.tree_util.tree_map(jnp.copy, cache), tok,
                        jnp.int32(pos))
        for run_b, run_a in zip(before["runs"], cache["runs"]):
            for key in ("mamba_conv", "mamba_h"):
                a = np.asarray(run_a[key][:, 0])
                b = np.asarray(run_b[key][:, 0])
                if pos < 2:
                    np.testing.assert_array_equal(a, b)
                else:
                    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(cache["start"]), [2, 0])


@pytest.mark.parametrize("arch", ["xlstm-1.3b", "llama-3.2-vision-11b"])
def test_families_without_row_starts_refuse_mixed_lengths(arch):
    mc = get_config(arch).reduced()
    model = build_model(mc)
    model.init_cache(2, 8, start=np.zeros(2, np.int32))    # one length: fine
    with pytest.raises(ValueError, match="one length per batch"):
        model.init_cache(2, 8, start=np.asarray([0, 3]))
