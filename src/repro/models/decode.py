"""Single-token decode steps + KV/state-cache construction for all families.

``decode_step(params, cache, token, pos)`` consumes and returns the cache
functionally (callers donate it for in-place updates). ``cache_struct``
returns the ShapeDtypeStruct tree used both to allocate zeros (serving) and
as abstract dry-run inputs (no allocation).

Mixed prompt lengths: a batch's prompts are left-padded to a common end
and the uniform-attention and hybrid caches carry each row's ``start``
(its first real position, (B,) int32). A step at ``pos < start[b]`` is a
pad step for row b: attention masks cache entries before ``start`` and
rotates at the row-relative position ``pos - start``, and the Mamba conv
buffer and state stay as they were, so each row's logits are those of
its prompt served alone. The xLSTM and VLM caches carry no ``start``:
those families serve one prompt length per batch.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import dtype_of, norm_apply
from repro.models.transformer import (_norm_kind, _unembed, apply_block,
                                      attn_runs)


def cache_struct(cfg: ModelConfig, batch: int, seq_len: int) -> Dict[str, Any]:
    """ShapeDtypeStruct tree of the decode cache."""
    dt = dtype_of(cfg.dtype)
    f32 = jnp.float32
    B, S, K, hd, L = batch, seq_len, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    sds = jax.ShapeDtypeStruct
    if cfg.family == "ssm":
        per = cfg.slstm_every
        n_seg = L // per
        H, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
        return {
            "m_c": sds((n_seg, per - 1, B, H, dh, dh), f32),
            "m_n": sds((n_seg, per - 1, B, H, dh), f32),
            "m_m": sds((n_seg, per - 1, B, H), f32),
            "s_c": sds((n_seg, B, H, dh), f32),
            "s_n": sds((n_seg, B, H, dh), f32),
            "s_m": sds((n_seg, B, H, dh), f32),
            "s_h": sds((n_seg, B, H, dh), f32),
        }
    if cfg.cross_attn_every:
        n_seg = L // cfg.cross_attn_every
        inner = cfg.cross_attn_every
        return {
            "k": sds((n_seg, inner, B, S, K, hd), dt),
            "v": sds((n_seg, inner, B, S, K, hd), dt),
            "xk": sds((n_seg, B, cfg.n_vision_tokens, K, hd), dt),
            "xv": sds((n_seg, B, cfg.n_vision_tokens, K, hd), dt),
        }
    # uniform attention archs: one cache tree per homogeneous run
    runs = []
    for (n, w, th) in attn_runs(cfg):
        c = {"k": sds((n, B, S, K, hd), dt), "v": sds((n, B, S, K, hd), dt)}
        if cfg.parallel_ssm:
            di = cfg.ssm.d_inner_mult * cfg.d_model
            W, N = cfg.ssm.conv_width, cfg.ssm.state_dim
            c["mamba_conv"] = sds((n, B, W - 1, di), f32)
            c["mamba_h"] = sds((n, B, di, N), f32)
        runs.append(c)
    return {"runs": runs, "start": sds((B,), jnp.int32)}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, start=None):
    """Zeroed cache; ``start`` (batch,) sets each row's first position
    (default 0 for every row)."""
    st = cache_struct(cfg, batch, seq_len)
    z = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), st)
    if cfg.family == "ssm":
        z["m_m"] = z["m_m"] - 1e30
        z["s_m"] = z["s_m"] - 1e30
    if start is not None:
        start = np.asarray(start, np.int32)
        if "start" in z:
            z["start"] = jnp.asarray(start)
        elif start.any():
            raise ValueError(
                f"{cfg.arch}: the {cfg.family} decode cache cannot mask "
                f"per-row prompt starts; serve prompts of one length per "
                f"batch")
    return z


def decode_step(params, cache, token, pos, cfg: ModelConfig, ctx=None
                ) -> Tuple[jax.Array, Any]:
    """token: (B, 1) int32; pos: scalar int32 (write index into the cache).

    Returns (logits (B, 1, V), new_cache).
    """
    x = params["embed"][token].astype(dtype_of(cfg.dtype))

    if cfg.family == "ssm":
        x, cache = _xlstm_decode(params, cache, x, cfg, ctx)
    elif cfg.cross_attn_every:
        x, cache = _vlm_decode(params, cache, x, pos, cfg, ctx)
    else:
        start = cache["start"]
        new_runs = []
        for run_p, run_c, (n, w, th) in zip(params["blocks"], cache["runs"],
                                            attn_runs(cfg)):
            def body(xc, inp, _w=w, _th=th):
                blk, c = inp
                y, c2 = apply_block(blk, xc, cfg, window=_w, theta=_th,
                                    ctx=ctx, mode="decode", cache=c, pos=pos,
                                    start=start)
                return y, c2

            x, c_new = jax.lax.scan(body, x, (run_p, run_c))
            new_runs.append(c_new)
        cache = {"runs": new_runs, "start": start}

    x = norm_apply(params["norm_f"], x, _norm_kind(cfg), cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    if ctx:
        logits = ctx.act_logits(logits)
    return logits, cache


def _vlm_decode(params, cache, x, pos, cfg, ctx):
    def seg_body(xc, inp):
        blks, cross, ck, cv, xk, xv = inp

        def inner_body(xi, binp):
            blk, c_k, c_v = binp
            y, c2 = apply_block(blk, xi, cfg, window=0, theta=cfg.rope_theta,
                                ctx=ctx, mode="decode",
                                cache={"k": c_k, "v": c_v}, pos=pos)
            return y, (c2["k"], c2["v"])

        xc, (nk, nv) = jax.lax.scan(inner_body, xc, (blks, ck, cv))
        h = norm_apply(cross["norm"], xc, "rms", cfg.norm_eps)
        q = h @ cross["attn"]["wq"].astype(h.dtype)
        B = q.shape[0]
        q = q.reshape(B, 1, cfg.n_kv_heads,
                      cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)
        o = attn.attention_scores_decode(q, xk, xv,
                                         pos=cfg.n_vision_tokens)
        o = o.reshape(B, 1, cfg.n_heads * cfg.head_dim)
        o = o @ cross["attn"]["wo"].astype(h.dtype)
        xc = xc + jnp.tanh(cross["gate"]).astype(xc.dtype) * o
        return xc, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        seg_body, x,
        (params["blocks"], params["cross"],
         cache["k"], cache["v"], cache["xk"], cache["xv"]))
    cache = dict(cache, k=nk, v=nv)
    return x, cache


def _xlstm_decode(params, cache, x, cfg, ctx):
    def seg_body(xc, inp):
        mblks, sblk, mc, mn, mm, sc, sn, sm, sh = inp

        def m_body(xi, binp):
            blk, c, n, m = binp
            st = xlstm_mod.MLSTMState(c=c, n=n, m=m)
            h = norm_apply(blk["norm"], xi, "rms", cfg.norm_eps)
            y, st = xlstm_mod.mlstm_step(blk["m"], h, st,
                                         n_heads=cfg.n_heads)
            return xi + y, (st.c, st.n, st.m)

        xc, mstates = jax.lax.scan(m_body, xc, (mblks, mc, mn, mm))
        h = norm_apply(sblk["norm"], xc, "rms", cfg.norm_eps)
        st = xlstm_mod.SLSTMState(c=sc, n=sn, m=sm, h=sh)
        y, st = xlstm_mod.slstm_step(sblk["s"], h, st, n_heads=cfg.n_heads)
        xc = xc + y
        return xc, (mstates, (st.c, st.n, st.m, st.h))

    x, (ms, ss) = jax.lax.scan(
        seg_body, x,
        (params["mblocks"], params["sblocks"], cache["m_c"], cache["m_n"],
         cache["m_m"], cache["s_c"], cache["s_n"], cache["s_m"],
         cache["s_h"]))
    cache = {"m_c": ms[0], "m_n": ms[1], "m_m": ms[2],
             "s_c": ss[0], "s_n": ss[1], "s_m": ss[2], "s_h": ss[3]}
    return x, cache


def prefill(params, batch, cfg: ModelConfig, ctx=None):
    """Full-sequence prefill. Returns (last-token logits, cache or None)."""
    from repro.models.transformer import forward
    h, caches = forward(params, batch, cfg, ctx, mode="prefill")
    logits = _unembed(params, cfg, h[:, -1:])
    if ctx:
        logits = ctx.act_logits(logits)
    if cfg.encoder_only:
        return logits, None
    return logits, caches
