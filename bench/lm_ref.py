"""Plain float32 reference of the served language models, and their weights.

The block is the one both configurations share: RMS-normed pre-norm
residual layers with grouped-query attention (rotary positions, causal,
optionally windowed) and a SwiGLU feed-forward; a hybrid layer adds a
Mamba-style selective-state head beside the attention and averages the
two normed outputs (Hymba's parallel heads). Everything here is
``jax.numpy`` in float32 at the highest matmul precision, one layer at a
time, with no cache and no batching tricks, and nothing is imported from
the program under test.

``init_params`` makes the weights the benchmark serves: one jitted call
on the device from the seed, in bfloat16, laid out as the program's
``LMServer`` takes them (one stacked group per run of layers that share
an attention window). The reference reads the same weights.

``quant="fp8"`` is the control: every matmul's weight (per output
column) and input (per row) rounded to float8 e4m3, the next precision
below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np


def layer_runs(cfg: dict) -> List[Tuple[int, int]]:
    """(length, window) of each run of consecutive layers that share a
    window (0 = global attention)."""
    pat = cfg["attn_pattern"]
    runs: List[List[int]] = []
    for i in range(cfg["n_layers"]):
        w = pat[i % len(pat)]
        if runs and runs[-1][1] == w:
            runs[-1][0] += 1
        else:
            runs.append([1, w])
    return [tuple(r) for r in runs]


def leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    """Per-layer weight shapes (name -> (shape, dtype name, init))."""
    D, H, K, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    F = cfg["d_ff"]
    out = {
        "norm1/w": ((D,), "bfloat16", "norm"),
        "attn/wq": ((D, H * hd), "bfloat16", "dense"),
        "attn/wk": ((D, K * hd), "bfloat16", "dense"),
        "attn/wv": ((D, K * hd), "bfloat16", "dense"),
        "attn/wo": ((H * hd, D), "bfloat16", "dense"),
        "norm2/w": ((D,), "bfloat16", "norm"),
        "ffn/wi": ((D, F), "bfloat16", "dense"),
        "ffn/wg": ((D, F), "bfloat16", "dense"),
        "ffn/wo": ((F, D), "bfloat16", "dense"),
    }
    if cfg.get("parallel_ssm"):
        s = cfg["ssm"]
        di, N, W = s["d_inner_mult"] * D, s["state_dim"], s["conv_width"]
        out.update({
            "mamba/w_in": ((D, 2 * di), "bfloat16", "dense"),
            "mamba/conv_w": ((W, di), "bfloat16", "conv"),
            "mamba/w_bcd": ((di, 2 * N + 1), "bfloat16", "dense"),
            "mamba/dt_bias": ((di,), "float32", "dt_bias"),
            "mamba/a_log": ((di, N), "float32", "a_log"),
            "mamba/d_skip": ((di,), "float32", "ones"),
            "mamba/w_out": ((di, D), "bfloat16", "dense"),
            "norm_attn_o/w": ((D,), "bfloat16", "norm"),
            "norm_ssm_o/w": ((D,), "bfloat16", "norm"),
        })
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        a, b = path.split("/")
        out.setdefault(a, {})[b] = v
    return out


def init_params(cfg: dict, seed: int):
    """The served weights, made on the default device by one jitted call.
    Matrices are uniform with the variance of a 1/sqrt(fan_in) normal;
    norm gains are small and random so that they matter."""
    import jax
    import jax.numpy as jnp

    D, V = cfg["d_model"], cfg["vocab"]
    shapes = leaf_shapes(cfg)
    runs = layer_runs(cfg)

    def leaf(key, shape, dtype, kind):
        dt = jnp.dtype(dtype)
        if kind == "dense":
            a = float(np.sqrt(3.0 / shape[0]))
            return jax.random.uniform(key, shape, dt, -a, a)
        if kind == "norm":
            return jax.random.uniform(key, shape, dt, -0.1, 0.1)
        if kind == "conv":
            return jax.random.uniform(key, shape, dt, -0.17, 0.17)
        if kind == "dt_bias":
            return jnp.full(shape, 0.5, dt)
        if kind == "a_log":
            n = shape[1]
            return jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32), shape)).astype(dt)
        return jnp.ones(shape, dt)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 4)
        a = float(np.sqrt(3.0 / D))
        p = {"embed": jax.random.uniform(ks[0], (V, D), jnp.bfloat16, -1, 1),
             "unembed": jax.random.uniform(ks[1], (V, D), jnp.bfloat16,
                                           -a, a),
             "norm_f": {"w": leaf(ks[2], (D,), "bfloat16", "norm")}}
        blocks, first = [], 0
        for n, _ in runs:
            flat = {}
            for j, (name, (shape, dtype, kind)) in enumerate(
                    sorted(shapes.items())):
                def one(i, _j=j, _s=shape, _d=dtype, _k=kind):
                    k = jax.random.fold_in(jax.random.fold_in(ks[3], i), _j)
                    return leaf(k, _s, _d, _k)
                flat[name] = jax.lax.map(one, first + jnp.arange(n))
            blocks.append(_nest(flat))
            first += n
        p["blocks"] = blocks
        return p

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _rms(x, w, eps):
    import jax.numpy as jnp
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(v + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x: (B, T, heads, d); rotate-half rotary embedding at positions 0..T-1."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, cfg, window, quant):
    import jax
    import jax.numpy as jnp
    B, T, _ = h.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = _mm(h, p["wq"], quant).reshape(B, T, H, hd)
    k = _mm(h, p["wk"], quant).reshape(B, T, K, hd)
    v = _mm(h, p["wv"], quant).reshape(B, T, K, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = H // K                     # query head h reads kv head h // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(T)[None, :]
    ok = (ki <= qi) & ((window <= 0) | (ki > qi - window))
    s = jnp.where(ok[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(B, T, H * hd), p["wo"], quant)


def _mamba(p, h, cfg, quant):
    import jax
    import jax.numpy as jnp
    B, T, D = h.shape
    xz = _mm(h, p["w_in"], quant)
    di = xz.shape[-1] // 2
    u, z = xz[..., :di], xz[..., di:]
    w = p["conv_w"].astype(jnp.float32)              # (W, di)
    W = w.shape[0]
    up = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[:, i:i + T] * w[i] for i in range(W)))
    N = (p["w_bcd"].shape[1] - 1) // 2
    bcd = _mm(u, p["w_bcd"], quant)
    Bt, Ct = bcd[..., :N], bcd[..., N:2 * N]
    dt = jax.nn.softplus(bcd[..., -1] + jnp.mean(p["dt_bias"]))   # (B, T)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))                  # (di, N)

    def step(s, t):
        s = jnp.exp(dt[:, t, None, None] * A) * s \
            + (dt[:, t, None] * u[:, t])[..., None] * Bt[:, t, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, Ct[:, t])

    _, y = jax.lax.scan(step, jnp.zeros((B, di, N), jnp.float32),
                        jnp.arange(T))
    y = jnp.swapaxes(y, 0, 1) + u * p["d_skip"].astype(jnp.float32)
    return _mm(y * jax.nn.silu(z), p["w_out"], quant)


def _swiglu(p, h, quant):
    import jax
    return _mm(jax.nn.silu(_mm(h, p["wg"], quant)) * _mm(h, p["wi"], quant),
               p["wo"], quant)


@functools.partial(__import__("jax").jit,
                   static_argnames=("cfg_items", "quant"))
def _layer(x, lp, window, *, cfg_items, quant):
    cfg = dict(cfg_items)
    eps = cfg["norm_eps"]
    h = _rms(x, lp["norm1"]["w"], eps)
    a = _attention(lp["attn"], h, cfg, window, quant)
    if "mamba" in lp:
        s = _mamba(lp["mamba"], h, cfg, quant)
        a = 0.5 * (_rms(a, lp["norm_attn_o"]["w"], eps)
                   + _rms(s, lp["norm_ssm_o"]["w"], eps))
    x = x + a
    return x + _swiglu(lp["ffn"], _rms(x, lp["norm2"]["w"], eps), quant)


@functools.partial(__import__("jax").jit, static_argnames=("eps", "quant"))
def _head(x, norm_w, unembed, *, eps, quant):
    return _mm(_rms(x, norm_w, eps), unembed.T, quant)


def _frozen(cfg: dict) -> tuple:
    keep = ("n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps")
    return tuple((k, cfg[k]) for k in keep)


def hidden_states(params, tokens: np.ndarray, cfg: dict,
                  quant: Optional[str] = None):
    """Final hidden states (B, T, D) of the full causal forward pass over
    ``tokens`` (B, T), computed layer by layer."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for run, (n, window) in zip(params["blocks"], layer_runs(cfg)):
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a, _i=i: a[_i], run)
                x = _layer(x, lp, jnp.int32(window),
                           cfg_items=_frozen(cfg), quant=quant)
    return x


def logits_at(params, tokens: np.ndarray, positions: np.ndarray,
              cfg: dict, quant: Optional[str] = None) -> np.ndarray:
    """Logits (B, P, V) at ``positions`` (B, P) of each row's forward pass,
    as float32 on the host."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, cfg, quant)
    x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
    with jax.default_matmul_precision("highest"):
        out = _head(x, params["norm_f"]["w"], params["unembed"],
                    eps=cfg["norm_eps"], quant=quant)
    return np.asarray(out, np.float32)
