"""Unified model: dense / MoE / SSM (xLSTM) / hybrid (hymba) / VLM / audio.

All layer stacks are ``lax.scan`` over stacked parameters (compile-time O(1)
in depth). Heterogeneous stacks use segment nesting:

- vlm:   scan over n_seg segments, each = inner scan over `cross_attn_every`
         self-attn blocks followed by one cross-attn block.
- ssm:   scan over n_seg segments, each = inner scan over (slstm_every - 1)
         mLSTM blocks followed by one sLSTM block.
- gemma3 local:global and hymba window patterns are handled *inside* a
  homogeneous scan via per-layer (window, rope_theta) scanned metadata.

Training loss uses sequence-chunked cross-entropy: full (B, S, V) logits are
never materialised (the unembed matmul is folded into a scan over sequence
chunks) — a large activation-memory win at 256k vocabularies.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (cross_entropy, dtype_of, embed_init,
                                 norm_apply, norm_init)

Params = Dict[str, Any]


def _norm_kind(cfg: ModelConfig) -> str:
    return "ln" if cfg.family == "audio" else "rms"


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig):
    """One transformer block (self-attn [+ssm] + ffn/moe)."""
    dt = dtype_of(cfg.param_dtype)
    nk = _norm_kind(cfg)
    ks = jax.random.split(key, 4)
    p = {"norm1": norm_init(cfg.d_model, nk, dt)}
    if cfg.family == "ssm":
        raise AssertionError("ssm handled separately")
    p["attn"] = attn.init_attn(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim, dt)
    if cfg.parallel_ssm:
        p["mamba"] = ssm_mod.init_mamba(ks[1], cfg.d_model, cfg.ssm, dt)
        p["norm_attn_o"] = norm_init(cfg.d_model, nk, dt)
        p["norm_ssm_o"] = norm_init(cfg.d_model, nk, dt)
    p["norm2"] = norm_init(cfg.d_model, nk, dt)
    if cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(ks[2], cfg.d_model, cfg.moe, cfg.act, dt)
    elif cfg.d_ff:
        p["ffn"] = ffn_mod.init_ffn(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def attn_runs(cfg: ModelConfig):
    """Group consecutive layers with equal (window, rope_theta) into runs.

    Returns a list of (length, window, theta) — windows stay STATIC so the
    blockwise-attention structure is sub-quadratic where the pattern says so.
    """
    L = cfg.n_layers
    tg = cfg.rope_theta_global or cfg.rope_theta
    metas = []
    for i in range(L):
        w = cfg.window_for_layer(i)
        metas.append((w, tg if w == 0 else cfg.rope_theta))
    runs = []
    for w, th in metas:
        if runs and runs[-1][1] == w and runs[-1][2] == th:
            runs[-1][0] += 1
        else:
            runs.append([1, w, th])
    return [tuple(r) for r in runs]


def apply_block(p, x, cfg: ModelConfig, *, window, theta, ctx,
                positions=None, mode: str = "train",
                cache: Optional[dict] = None, pos=None, start=None):
    """One block. mode: train|prefill (full-seq) or decode (one token).

    In decode, ``start`` (B,) gives each row's first position (a hybrid
    block needs it): a row before its start takes a pad step, which
    attention masks from later steps and which leaves the Mamba state
    unchanged.

    Returns (x, new_cache_entry) where new_cache_entry is None in train mode.
    """
    nk, eps = _norm_kind(cfg), cfg.norm_eps
    h = norm_apply(p["norm1"], x, nk, eps)
    shard = (lambda t: ctx.act_kv(t)) if ctx else None
    layout = ctx.attn_layout(cfg.n_heads, cfg.n_kv_heads) if ctx \
        else "grouped"
    new_cache = {}
    if mode in ("train", "prefill"):
        a_out, (k, v) = attn.attn_forward(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=theta, positions=positions,
            causal=not cfg.encoder_only, window=window, shard=shard,
            layout=layout,
            shard_qblocks=(lambda t: ctx.act_qblocks(t)) if ctx else None)
        if mode == "prefill":
            new_cache["k"], new_cache["v"] = k, v
    else:
        a_out, ck, cv = attn.attn_decode(
            p["attn"], h, cache["k"], cache["v"], pos=pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=theta, window=window,
            shard=shard, start=start)
        new_cache["k"], new_cache["v"] = ck, cv

    if cfg.parallel_ssm:
        if mode in ("train", "prefill"):
            s_out, s_state = ssm_mod_forward_with_state(p["mamba"], h, cfg)
            if mode == "prefill":
                new_cache["mamba_conv"] = s_state.conv
                new_cache["mamba_h"] = s_state.h
        else:
            st = ssm_mod.MambaState(conv=cache["mamba_conv"],
                                    h=cache["mamba_h"])
            s_out, st = ssm_mod.mamba_step(
                p["mamba"], h, st, cfg=cfg.ssm, live=pos >= start)
            new_cache["mamba_conv"], new_cache["mamba_h"] = st.conv, st.h
        a_out = 0.5 * (norm_apply(p["norm_attn_o"], a_out, nk, eps)
                       + norm_apply(p["norm_ssm_o"], s_out, nk, eps))
    x = x + a_out
    if ctx:
        x = ctx.act_btd(x)

    h2 = norm_apply(p["norm2"], x, nk, eps)
    if cfg.moe is not None:
        f_out = moe_mod.moe_forward(
            p["moe"], h2, cfg=cfg.moe, act=cfg.act, mesh=ctx.mesh,
            batch_axes=ctx.batch_axes,
            fsdp_axis=ctx.fsdp_axis or "data",
            weight_stationary=ctx.moe_weight_stationary) if ctx else \
            moe_mod.moe_ref(p["moe"], h2, cfg=cfg.moe, act=cfg.act)
    elif cfg.d_ff:
        f_out = ffn_mod.ffn_forward(
            p["ffn"], h2, cfg.act,
            shard=(lambda t: ctx.act_ff(t)) if ctx else None)
    else:
        f_out = 0.0
    x = x + f_out
    if ctx:
        x = ctx.act_btd(x)
    return x, (new_cache or None)


def ssm_mod_forward_with_state(params, x, cfg: ModelConfig):
    """mamba_forward + final state (for prefill)."""
    y = ssm_mod.mamba_forward(params, x, cfg=cfg.ssm)
    # recompute final state cheaply from the last conv_width tokens + rerun?
    # For prefill we need exact state: run a short suffix pass — the scan in
    # mamba_forward already has it, so we expose it via the step path on the
    # last token only when required. To keep one code path, recompute state
    # by scanning the final chunk is equivalent; here we fold it directly:
    st = ssm_mod.mamba_prefill_state(params, x, cfg=cfg.ssm)
    return y, st


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------


def init_xlstm_mblock(key, cfg: ModelConfig):
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {"norm": norm_init(cfg.d_model, "rms", dt),
            "m": xlstm_mod.init_mlstm(k1, cfg.d_model, cfg.n_heads, dt)}


def init_xlstm_sblock(key, cfg: ModelConfig):
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {"norm": norm_init(cfg.d_model, "rms", dt),
            "s": xlstm_mod.init_slstm(k1, cfg.d_model, cfg.n_heads, dt)}


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, rng) -> Params:
    dt = dtype_of(cfg.param_dtype)
    keys = jax.random.split(rng, 8)
    p: Params = {}
    p["embed"] = embed_init(keys[0], cfg.vocab, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(keys[1], cfg.vocab, cfg.d_model, dt)
    p["norm_f"] = norm_init(cfg.d_model, _norm_kind(cfg), dt)

    if cfg.family == "ssm":
        per = cfg.slstm_every or (cfg.n_layers + 1)
        n_seg, rem = divmod(cfg.n_layers, per)
        assert rem == 0, "ssm stack must divide into (m*(per-1)+s) segments"
        mk = jax.random.split(keys[2], n_seg * (per - 1)).reshape(
            n_seg, per - 1, 2)
        p["mblocks"] = jax.vmap(jax.vmap(
            lambda k: init_xlstm_mblock(k, cfg)))(mk)
        sk = jax.random.split(keys[3], n_seg)
        p["sblocks"] = jax.vmap(lambda k: init_xlstm_sblock(k, cfg))(sk)
        return p

    if cfg.cross_attn_every:
        n_seg, rem = divmod(cfg.n_layers, cfg.cross_attn_every)
        assert rem == 0
        bk = jax.random.split(keys[2], n_seg * cfg.cross_attn_every).reshape(
            n_seg, cfg.cross_attn_every, 2)
        p["blocks"] = jax.vmap(jax.vmap(lambda k: init_block(k, cfg)))(bk)
        ck = jax.random.split(keys[3], n_seg)

        def init_cross(k):
            kk = jax.random.split(k, 2)
            return {"norm": norm_init(cfg.d_model, "rms", dt),
                    "attn": attn.init_attn(kk[0], cfg.d_model, cfg.n_heads,
                                           cfg.n_kv_heads, cfg.head_dim, dt),
                    "gate": jnp.zeros((1,), jnp.float32)}

        p["cross"] = jax.vmap(init_cross)(ck)
        return p

    # homogeneous runs of equal (window, theta): one stacked scan per run
    runs = attn_runs(cfg)
    all_keys = jax.random.split(keys[2], cfg.n_layers)
    blocks, off = [], 0
    for (n, _, _) in runs:
        ks_run = all_keys[off:off + n]
        blocks.append(jax.vmap(lambda k: init_block(k, cfg))(ks_run))
        off += n
    p["blocks"] = blocks
    return p


def _embed_in(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    if cfg.embedding_inputs:
        return batch["embeds"]
    x = params["embed"][batch["tokens"]]
    return x.astype(dtype_of(cfg.dtype))


def _unembed(params, cfg: ModelConfig, x):
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return x @ w.astype(x.dtype).T


def forward(params: Params, batch, cfg: ModelConfig, ctx=None,
            mode: str = "train"):
    """Full-sequence forward. Returns (h_final, aux) where h_final is the
    pre-unembed hidden state; aux carries the prefill cache if requested."""
    x = _embed_in(params, cfg, batch)
    if ctx:
        x = ctx.act_btd(x)
    S = x.shape[1]
    positions = jnp.arange(S)
    collect = mode == "prefill"

    if cfg.family == "ssm":
        x, aux = _xlstm_stack(params, x, cfg, ctx, collect)
    elif cfg.cross_attn_every:
        x, aux = _vlm_stack(params, x, batch["vision_embeds"], cfg, ctx,
                            positions, collect)
    else:
        aux = []
        for run_p, (n, w, th) in zip(params["blocks"], attn_runs(cfg)):
            def body(xc, blk, _w=w, _th=th):
                y, c = apply_block(blk, xc, cfg, window=_w, theta=_th,
                                   ctx=ctx, positions=positions,
                                   mode="prefill" if collect else "train")
                return y, c

            x, caches = _scan_run(body, x, run_p, cfg, n)
            aux.append(caches)
        if not collect:
            aux = None
    x = norm_apply(params["norm_f"], x, _norm_kind(cfg), cfg.norm_eps)
    return x, aux


def _scan_run(body, x, run_p, cfg: ModelConfig, n: int):
    """Scan a homogeneous run; two-level (grouped) remat when configured.

    Grouped remat (e.g. nemotron: 96 = 12 groups x 8 layers) saves one
    residual per GROUP instead of per layer; group internals recompute during
    backward with per-layer remat — peak saved-activation memory drops
    ~n/groups x at ~2x recompute of the inner forward.
    """
    g = cfg.remat_groups
    if g and n % g == 0 and n > g:
        inner = n // g
        grouped = jax.tree_util.tree_map(
            lambda p: p.reshape((g, inner) + p.shape[1:]), run_p)

        def outer(xc, gp):
            return jax.lax.scan(_remat(body, cfg), xc, gp)

        x, caches = jax.lax.scan(jax.checkpoint(outer), x, grouped)
        caches = jax.tree_util.tree_map(
            lambda c: c.reshape((n,) + c.shape[2:]) if c is not None else c,
            caches)
        return x, caches
    return jax.lax.scan(_remat(body, cfg), x, run_p)


def _vlm_stack(params, x, vis, cfg, ctx, positions, collect):
    shard = (lambda t: ctx.act_kv(t)) if ctx else None

    def seg_body(xc, inp):
        blks, cross = inp

        def inner_body(xi, blk):
            y, c = apply_block(blk, xi, cfg, window=0, theta=cfg.rope_theta,
                               ctx=ctx, positions=positions,
                               mode="prefill" if collect else "train")
            return y, c

        xc, caches = jax.lax.scan(_remat(inner_body, cfg), xc, blks)
        h = norm_apply(cross["norm"], xc, "rms", cfg.norm_eps)
        c_out = attn.cross_attn_forward(
            cross["attn"], h, vis, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, shard=shard)
        xc = xc + jnp.tanh(cross["gate"]).astype(xc.dtype) * c_out
        return xc, caches

    x, caches = jax.lax.scan(seg_body, x,
                             (params["blocks"], params["cross"]))
    return x, caches


def _xlstm_stack(params, x, cfg, ctx, collect):
    chunk = cfg.ssm.chunk if cfg.ssm else 128

    def seg_body(xc, inp):
        mblks, sblk = inp

        def m_body(xi, blk):
            h = xlstm_mod.mlstm_forward(
                blk["m"], norm_apply(blk["norm"], xi, "rms", cfg.norm_eps),
                n_heads=cfg.n_heads, chunk=chunk)
            y = xi + h
            if ctx:
                y = ctx.act_btd(y)
            return y, None

        xc, _ = jax.lax.scan(_remat(m_body, cfg), xc, mblks)
        h_in = norm_apply(sblk["norm"], xc, "rms", cfg.norm_eps)
        if ctx is not None and ctx.slstm_local_grad:
            h = xlstm_mod.slstm_forward_sharded(
                sblk["s"], h_in, n_heads=cfg.n_heads, mesh=ctx.mesh,
                batch_axes=ctx.batch_axes)
        else:
            h = xlstm_mod.slstm_forward(sblk["s"], h_in,
                                        n_heads=cfg.n_heads)
        xc = xc + h
        if ctx:
            xc = ctx.act_btd(xc)
        return xc, None

    x, _ = jax.lax.scan(seg_body, x, (params["mblocks"], params["sblocks"]))
    # prefill state for ssm is recomputed by the decode driver (serve.engine)
    return x, None


# ---------------------------------------------------------------------------
# Loss (chunked CE)
# ---------------------------------------------------------------------------


def loss_fn(params: Params, batch, cfg: ModelConfig, ctx=None,
            ce_chunk: int = 1024):
    """Next-token (or masked, for encoder) CE loss with chunked unembed."""
    h, _ = forward(params, batch, cfg, ctx, mode="train")
    if cfg.encoder_only:
        labels = batch["labels"]
        h_in, lab = h, labels
    else:
        h_in = h[:, :-1]
        lab = batch["labels"][:, 1:] if "labels" in batch \
            else batch["tokens"][:, 1:]
    B, S, D = h_in.shape
    ce_chunk = min(ce_chunk, S)
    pad = (-S) % ce_chunk
    if pad:
        h_in = jnp.pad(h_in, ((0, 0), (0, pad), (0, 0)))
        lab = jnp.pad(lab, ((0, 0), (0, pad)), constant_values=-1)
    nc = (S + pad) // ce_chunk
    h_c = h_in.reshape(B, nc, ce_chunk, D).swapaxes(0, 1)
    l_c = lab.reshape(B, nc, ce_chunk).swapaxes(0, 1)

    def body(acc, inp):
        hc, lc = inp
        logits = _unembed(params, cfg, hc)
        if ctx:
            logits = ctx.act_logits(logits)
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        return (acc[0] + jnp.sum((lse - ll) * mask),
                acc[1] + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (h_c, l_c))
    return tot / jnp.maximum(cnt, 1.0)


def logits_fn(params: Params, batch, cfg: ModelConfig, ctx=None):
    """Full logits (for tests / small-scale evaluation)."""
    h, _ = forward(params, batch, cfg, ctx, mode="train")
    return _unembed(params, cfg, h)
