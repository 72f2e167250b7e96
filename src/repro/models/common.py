"""Shared building blocks: norms, RoPE, initialisers, dtype policy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    """Truncated-normal fan-in init."""
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    w = jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out), jnp.float32)
    return (w * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d), jnp.float32)
    return (w * 0.02).astype(dtype)


def rms_norm(x, w, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm_apply(params, x, kind: str, eps: float):
    if kind == "ln":
        return layer_norm(x, params["w"], params["b"], eps)
    return rms_norm(x, params["w"], eps)


def norm_init(d: int, kind: str, dtype):
    if kind == "ln":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.zeros((d,), dtype)}  # rms: stored as (1 + w)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim/2,)


def apply_rope(x, positions, theta):
    """x: (..., S, ..., head_dim) with positions broadcastable to x's S dim.

    positions: (S,) or (B, S). x layout: (B, S, K, G, d) or (B, S, K, d).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (d/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, d/2)
    # reshape angles to broadcast over the head dims between S and d
    for _ in range(x.ndim - 3):
        angles = angles[..., None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def act_fn(name: str):
    if name in ("swiglu",):
        return jax.nn.silu
    if name == "geglu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


def cross_entropy(logits, labels, ignore_id: int = -1):
    """Stable CE in fp32 over (possibly sharded) vocab dim. Returns mean loss."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
