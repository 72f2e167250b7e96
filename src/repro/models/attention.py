"""Attention: blockwise (flash-style, online-softmax) full-sequence attention
with causal / sliding-window / bidirectional masks, GQA grouped heads,
single-token decode against a KV cache, and cross-attention.

FLOPs honesty: the blockwise path only visits (q-block, kv-block) pairs that
can contain unmasked entries, so causal attention costs ~S^2/2 and windowed
attention ~S*(W+Bq) — the compiled HLO reflects the sub-quadratic structure
instead of a dense masked S x S matmul.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_rope, dense_init

NEG_INF = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array  # (D, H*hd)
    wk: jax.Array  # (D, K*hd)
    wv: jax.Array  # (D, K*hd)
    wo: jax.Array  # (H*hd, D)


def init_attn(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              dtype) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype,
                         scale=1.0 / np.sqrt(n_heads * head_dim)),
    }


def _block_pairs(n_q: int, n_kv: int, block_q: int, block_kv: int,
                 causal: bool, window: int) -> np.ndarray:
    """Static list of (qi, kj) block pairs that may contain unmasked entries."""
    pairs = []
    for qi in range(n_q):
        q_lo, q_hi = qi * block_q, qi * block_q + block_q - 1
        for kj in range(n_kv):
            k_lo, k_hi = kj * block_kv, kj * block_kv + block_kv - 1
            if causal and k_lo > q_hi:
                continue
            if window > 0 and k_hi < q_lo - window + 1:
                continue
            pairs.append((qi, kj))
    return np.asarray(pairs, dtype=np.int32)


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        block_q: int = 512, block_kv: int = 512,
                        q_offset: int = 0):
    """Online-softmax attention over blocks.

    q: (B, Sq, K, G, d)   grouped GQA layout (H = K*G)
    k, v: (B, Skv, K, d)
    window: 0 == unlimited; else causal sliding window of that many positions.
    q_offset: absolute position of q[0] relative to k[0] (for windowed decode
    chunks); masks use absolute positions q_pos = i + q_offset.
    Returns (B, Sq, K, G, d).
    """
    B, Sq, K, G, d = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    # pad sequence dims to block multiples
    pq = (-Sq) % block_q
    pk = (-Skv) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    Sqp, Skvp = Sq + pq, Skv + pk
    n_q, n_kv = Sqp // block_q, Skvp // block_kv

    pairs = _block_pairs(n_q, n_kv, block_q, block_kv, causal, window)
    scale = 1.0 / np.sqrt(d)

    out0 = jnp.zeros((B, Sqp, K, G, d), jnp.float32)
    m0 = jnp.full((B, K, G, Sqp), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sqp), jnp.float32)

    q_ids_blk = jnp.arange(block_q)
    k_ids_blk = jnp.arange(block_kv)

    def body(carry, pair):
        out, m, l = carry
        qi, kj = pair[0], pair[1]
        qs, ks_ = qi * block_q, kj * block_kv
        qb = jax.lax.dynamic_slice_in_dim(q, qs, block_q, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, ks_, block_kv, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, ks_, block_kv, axis=1)
        # scores: (B, K, G, bq, bkv)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb.astype(jnp.float32),
                       kb.astype(jnp.float32)) * scale
        q_pos = qs + q_ids_blk + q_offset            # absolute positions
        k_pos = ks_ + k_ids_blk
        mask = jnp.ones((block_q, block_kv), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        # mask out kv padding
        mask &= (ks_ + k_ids_blk < Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)

        mb = jax.lax.dynamic_slice_in_dim(m, qs, block_q, axis=3)
        lb = jax.lax.dynamic_slice_in_dim(l, qs, block_q, axis=3)
        ob = jax.lax.dynamic_slice_in_dim(out, qs, block_q, axis=1)

        m_new = jnp.maximum(mb, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mb - m_new)
        l_new = lb * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p, vb.astype(jnp.float32))
        ob_new = ob * jnp.transpose(corr, (0, 3, 1, 2))[..., None] + pv

        out = jax.lax.dynamic_update_slice_in_dim(out, ob_new, qs, axis=1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, qs, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, qs, axis=3)
        return (out, m, l), None

    (out, m, l), _ = jax.lax.scan(body, (out0, m0, l0), jnp.asarray(pairs))
    denom = jnp.transpose(l, (0, 3, 1, 2))[..., None]
    out = out / jnp.maximum(denom, 1e-30)
    if pq:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def qblock_attention(q, k, v, *, causal: bool, window: int = 0,
                     block_q: int = 512, block_kv: int = 512,
                     shard_blocks=None):
    """Query-block-PARALLEL attention: all query blocks are a batch-like dim
    (shardable over the model axis) instead of a sequential scan.

    Used when neither KV nor Q heads divide the model axis (hymba: 25 heads)
    — head sharding is impossible, but the q-block dim shards cleanly.
    Windowed layers gather a per-block KV window (static indices, fully
    local compute). Global layers scan KV blocks with online softmax and
    causal masking (≤2x the triangle FLOPs, in exchange for n-way sharding).

    q: (B, S, K, G, d); k, v: (B, S, K, d). Returns (B, S, K, G, d).
    """
    B, S, K, G, d = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, S)
    pad = (-S) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    Sp = S + pad
    nb = Sp // block_q
    qb = q.reshape(B, nb, block_q, K, G, d)
    if shard_blocks is not None:
        qb = shard_blocks(qb)
    scale = 1.0 / np.sqrt(d)
    q_pos = (jnp.arange(nb) * block_q)[:, None] + jnp.arange(block_q)[None]

    if causal and window > 0:
        wp = window + block_q
        base = (jnp.arange(nb) * block_q)[:, None] - window \
            + jnp.arange(wp)[None, :]                     # (nb, wp)
        idx = jnp.clip(base, 0, Skv - 1)
        kw = k[:, idx]                                    # (B, nb, wp, K, d)
        vw = v[:, idx]
        s = jnp.einsum("bnqkgd,bnwkd->bnkgqw", qb.astype(jnp.float32),
                       kw.astype(jnp.float32)) * scale
        mask = (base[:, None, :] <= q_pos[..., None]) \
            & (base[:, None, :] > q_pos[..., None] - window) \
            & (base >= 0)[:, None, :] & (base < Skv)[:, None, :]
        s = jnp.where(mask[None, :, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bnkgqw,bnwkd->bnqkgd", p, vw.astype(jnp.float32))
    else:
        block_kv = min(block_kv, Skv)
        pk = (-Skv) % block_kv
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        n_kv = (Skv + pk) // block_kv
        k_ids = jnp.arange(block_kv)

        def body(carry, j):
            acc, m, l = carry
            ks_ = j * block_kv
            kb = jax.lax.dynamic_slice_in_dim(k, ks_, block_kv, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ks_, block_kv, axis=1)
            s = jnp.einsum("bnqkgd,bskd->bnkgqs", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            k_pos = ks_ + k_ids
            mask = (k_pos[None, None, :] < Skv)
            if causal:
                mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
            s = jnp.where(mask[None, :, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(pexp, axis=-1)
            pv = jnp.einsum("bnkgqs,bskd->bnqkgd", pexp,
                            vb.astype(jnp.float32))
            acc = acc * jnp.moveaxis(corr, -1, 2)[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, nb, block_q, K, G, d), jnp.float32)
        m0 = jnp.full((B, nb, K, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, nb, K, G, block_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                      jnp.arange(n_kv))
        out = acc / jnp.maximum(jnp.moveaxis(l, -1, 2)[..., None], 1e-30)

    out = out.reshape(B, Sp, K, G, d)[:, :S]
    return out.astype(q.dtype)


def attention_scores_decode(q, k_cache, v_cache, *, pos, window: int = 0,
                            start=None):
    """Single-token attention against a cache.

    q: (B, 1, K, G, d); k_cache/v_cache: (B, S, K, d); pos: scalar int —
    number of valid cache entries (the new token's absolute position + 1).
    start: optional (B,) int — each row's first valid cache entry (a
    left-padded prompt's first token); entries before it are masked.

    Mixed precision via preferred_element_type (bf16 inputs, fp32
    accumulation) — casting the cache would let XLA hoist an fp32 convert of
    the ENTIRE stacked cache out of the layer loop (2x cache memory+traffic;
    observed on qwen3 decode, see EXPERIMENTS.md §Perf).
    """
    B, _, K, G, d = q.shape
    S = k_cache.shape[1]
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * scale
    ids = jnp.arange(S)[None, :]
    valid = ids < pos
    if window > 0:
        valid &= ids > pos - 1 - window
    if start is not None:
        valid = valid & (ids >= start[:, None])             # (B, S)
    else:
        valid = jnp.broadcast_to(valid, (B, S))
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _split_heads(x, n_heads, n_kv, head_dim):
    """(B, S, H*hd) -> grouped (B, S, K, G, hd)."""
    B, S, _ = x.shape
    G = n_heads // n_kv
    return x.reshape(B, S, n_kv, G, head_dim)


def _split_kv(x, n_kv, head_dim):
    B, S, _ = x.shape
    return x.reshape(B, S, n_kv, head_dim)


def attn_forward(params, x, *, n_heads, n_kv_heads, head_dim,
                 rope_theta, positions=None, causal=True, window: int = 0,
                 block_q=512, block_kv=512, shard=None,
                 layout: str = "grouped", shard_qblocks=None):
    """Full-sequence attention (train / prefill). Returns (out, (k, v)).

    layout="expand": KV heads are replicated up to n_heads so the head dim
    can be tensor-sharded when n_kv_heads does not divide the model axis
    (grok/qwen/internlm/nemotron/llama all have K=4..8 < 16). The returned
    cache keeps the compact (B, S, K, hd) layout.
    """
    B, S, D = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if positions is None:
        positions = jnp.arange(S)
    q = _split_heads(q, n_heads, n_kv_heads, head_dim)
    k = _split_kv(k, n_kv_heads, head_dim)
    v = _split_kv(v, n_kv_heads, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    cache = (k, v)
    if layout == "qblock":
        out = qblock_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               shard_blocks=shard_qblocks)
        out = out.reshape(B, S, n_heads * head_dim)
        return out @ params["wo"].astype(x.dtype), cache
    if layout == "expand":
        G = n_heads // n_kv_heads
        q = q.reshape(B, S, n_heads, 1, head_dim)
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    if shard is not None:
        q, k, v = shard(q), shard(k), shard(v)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_kv=block_kv)
    out = out.reshape(B, S, n_heads * head_dim)
    return out @ params["wo"].astype(x.dtype), cache


def attn_decode(params, x, cache_k, cache_v, *, pos, n_heads, n_kv_heads,
                head_dim, rope_theta, window: int = 0, shard=None,
                start=None):
    """One-token decode. x: (B, 1, D); cache: (B, S, K, hd). pos: scalar —
    index where the new token is written. start: optional (B,) — each
    row's first cache index; keys before it are masked and q/k rotate at
    the row-relative position ``pos - start``. Returns (out, cache_k,
    cache_v)."""
    B, _, D = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    q = _split_heads(q, n_heads, n_kv_heads, head_dim)
    k = _split_kv(k, n_kv_heads, head_dim)
    v = _split_kv(v, n_kv_heads, head_dim)
    if rope_theta is not None:
        p = jnp.full((1,), pos) if start is None \
            else (pos - start)[:, None]                     # (B, 1)
        q = apply_rope(q, p, rope_theta)
        k = apply_rope(k, p, rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    if shard is not None:
        cache_k, cache_v = shard(cache_k), shard(cache_v)
    out = attention_scores_decode(q, cache_k, cache_v, pos=pos + 1,
                                  window=window, start=start)
    out = out.reshape(B, 1, n_heads * head_dim)
    return out @ params["wo"].astype(x.dtype), cache_k, cache_v


def cross_attn_forward(params, x, kv_src, *, n_heads, n_kv_heads, head_dim,
                       shard=None):
    """Cross attention: queries from x (B,S,D), keys/values from kv_src
    (B, T, D). Bidirectional (no mask). Returns out (B,S,D)."""
    B, S, D = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = kv_src @ params["wk"].astype(kv_src.dtype)
    v = kv_src @ params["wv"].astype(kv_src.dtype)
    q = _split_heads(q, n_heads, n_kv_heads, head_dim)
    k = _split_kv(k, n_kv_heads, head_dim)
    v = _split_kv(v, n_kv_heads, head_dim)
    if shard is not None:
        q, k, v = shard(q), shard(k), shard(v)
    out = blockwise_attention(q, k, v, causal=False, window=0,
                              block_q=512, block_kv=512)
    out = out.reshape(B, S, n_heads * head_dim)
    return out @ params["wo"].astype(x.dtype)
