"""Mamba-style selective SSM head (used by hymba's parallel attn+SSM layers).

Training/prefill uses a chunkwise formulation: the sequential scan runs over
chunks (T/chunk steps) with dense intra-chunk compute, keeping the while-loop
trip count low and compute dense. Decode is a single recurrent step with
carried (conv_state, ssm_state).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init


def init_mamba(key, d_model: int, cfg, dtype) -> dict:
    di = cfg.d_inner_mult * d_model
    N = cfg.state_dim
    ks = jax.random.split(key, 6)
    # S4D-real initialisation for A
    a = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "w_in": dense_init(ks[0], d_model, 2 * di, dtype),   # x and z branches
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, di), jnp.float32)
                   * 0.1).astype(dtype),
        "w_bcd": dense_init(ks[2], di, 2 * N + 1, dtype),     # B, C, dt
        "dt_bias": jnp.ones((di,), jnp.float32) * 0.5,
        "a_log": jnp.log(a),                                   # (di, N)
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(ks[3], di, d_model, dtype),
    }


def _conv_causal(x, w):
    """Depthwise causal conv. x: (B, T, di); w: (W, di)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None, :]
    return out


def _ssm_inputs(params, u):
    """Common gating/projection math. u: (B, T, di) post-conv.

    Returns (dA (B,T,di,N) decay, dBx (B,T,di,N) input, C (B,T,N))."""
    N = (params["w_bcd"].shape[1] - 1) // 2
    bcd = u @ params["w_bcd"].astype(u.dtype)
    B_t = bcd[..., :N].astype(jnp.float32)                 # (B,T,N)
    C_t = bcd[..., N:2 * N].astype(jnp.float32)
    dt = jax.nn.softplus(bcd[..., -1].astype(jnp.float32)
                         + params["dt_bias"].mean())        # (B,T)
    A = -jnp.exp(params["a_log"])                           # (di, N)
    dA = jnp.exp(dt[..., None, None] * A[None, None])       # (B,T,di,N)
    dBx = (dt[..., None] * u.astype(jnp.float32))[..., None] \
        * B_t[..., None, :]                                 # (B,T,di,N)
    return dA, dBx, C_t


def mamba_forward(params, x, *, cfg):
    """Full-sequence forward. x: (B, T, D) -> (B, T, D).

    Chunked: sequential scan over T/chunk chunks; inside a chunk the
    recurrence h_t = dA_t h_{t-1} + dBx_t is unrolled via cumulative products
    in log space is avoided — we scan timesteps inside the chunk (cheap dense
    ops, static small trip count) to stay numerically exact.

    cfg.chunk_local=True computes projections/conv/gates INSIDE the chunk
    scan so no (B, T, di, N) tensor is ever materialised (peak activation
    memory drops by T/chunk); the baseline precomputes them for the whole
    sequence (the direct port of the reference implementation).
    """
    if getattr(cfg, "chunk_local", False):
        return _mamba_forward_chunk_local(params, x, cfg=cfg)
    B, T, D = x.shape
    di = cfg.d_inner_mult * D
    L = min(cfg.chunk, T)
    pad = (-T) % L
    xz = x @ params["w_in"].astype(x.dtype)
    u, z = jnp.split(xz, 2, axis=-1)
    u = _conv_causal(u, params["conv_w"].astype(u.dtype))
    u = jax.nn.silu(u)
    dA, dBx, C_t = _ssm_inputs(params, u)
    if pad:
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0), (0, 0)),
                     constant_values=1.0)
        dBx = jnp.pad(dBx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_t = jnp.pad(C_t, ((0, 0), (0, pad), (0, 0)))
    nC = (T + pad) // L

    def chunk_body(h, inp):
        dA_c, dBx_c, C_c = inp          # (B, L, di, N), (B, L, N)
        ys = []
        for t in range(L):              # static unroll inside chunk
            h = dA_c[:, t] * h + dBx_c[:, t]
            ys.append(jnp.einsum("bdn,bn->bd", h, C_c[:, t]))
        return h, jnp.stack(ys, axis=1)  # (B, L, di)

    h0 = jnp.zeros((B, di, N_state(params)), jnp.float32)
    xs = (dA.reshape(B, nC, L, di, -1).swapaxes(0, 1),
          dBx.reshape(B, nC, L, di, -1).swapaxes(0, 1),
          C_t.reshape(B, nC, L, -1).swapaxes(0, 1))
    _, y = jax.lax.scan(chunk_body, h0, xs)
    y = y.swapaxes(0, 1).reshape(B, T + pad, di)[:, :T]
    y = y + u.astype(jnp.float32) * params["d_skip"][None, None]
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ params["w_out"].astype(x.dtype)


def _mamba_forward_chunk_local(params, x, *, cfg):
    """Memory-optimised path: everything is computed per chunk inside the
    scan; the conv tail (W-1 tokens) is carried between chunks."""
    B, T, D = x.shape
    di = cfg.d_inner_mult * D
    N = N_state(params)
    W = params["conv_w"].shape[0]
    L = min(cfg.chunk, T)
    pad = (-T) % L
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    nC = (T + pad) // L
    xs = xp.reshape(B, nC, L, D).swapaxes(0, 1)          # (nC, B, L, D)

    w_in = params["w_in"]
    conv_w = params["conv_w"]

    def chunk_body(carry, x_c):
        h, tail = carry                                   # tail: (B, W-1, di)
        xz = x_c @ w_in.astype(x_c.dtype)                 # (B, L, 2di)
        u, z = jnp.split(xz, 2, axis=-1)
        u_ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
        conv = jnp.zeros_like(u)
        for i in range(W):
            conv = conv + u_ext[:, i:i + L] * conv_w[i][None, None].astype(
                u.dtype)
        uc = jax.nn.silu(conv)
        dA, dBx, C_t = _ssm_inputs(params, uc)
        ys = []
        for t in range(L):
            h = dA[:, t] * h + dBx[:, t]
            ys.append(jnp.einsum("bdn,bn->bd", h, C_t[:, t]))
        y = jnp.stack(ys, axis=1)                         # (B, L, di) f32
        y = y + uc.astype(jnp.float32) * params["d_skip"][None, None]
        y = y.astype(x_c.dtype) * jax.nn.silu(z)
        out = y @ params["w_out"].astype(x_c.dtype)       # (B, L, D)
        new_tail = u_ext[:, L:L + W - 1]
        return (h, new_tail.astype(jnp.float32)), out

    h0 = jnp.zeros((B, di, N), jnp.float32)
    tail0 = jnp.zeros((B, W - 1, di), jnp.float32)
    _, ys = jax.lax.scan(chunk_body, (h0, tail0), xs)
    out = ys.swapaxes(0, 1).reshape(B, T + pad, D)
    return out[:, :T]


def N_state(params) -> int:
    return params["a_log"].shape[1]


class MambaState(NamedTuple):
    conv: jax.Array  # (B, W-1, di)
    h: jax.Array     # (B, di, N)


def mamba_init_state(params, batch: int, dtype=jnp.float32) -> MambaState:
    W, di = params["conv_w"].shape
    N = N_state(params)
    return MambaState(conv=jnp.zeros((batch, W - 1, di), dtype),
                      h=jnp.zeros((batch, di, N), jnp.float32))


def mamba_step(params, x, state: MambaState, *, cfg, live=None):
    """Single-token decode. x: (B, 1, D). Returns (y (B,1,D), new_state).

    live: optional (B,) bool — rows where it is False take a pad step
    (a left-padded prompt's lead-in): their conv buffer and SSM state
    come back unchanged."""
    B = x.shape[0]
    xz = x @ params["w_in"].astype(x.dtype)
    u, z = jnp.split(xz, 2, axis=-1)                    # (B, 1, di)
    conv_in = jnp.concatenate([state.conv, u.astype(state.conv.dtype)], axis=1)
    w = params["conv_w"].astype(jnp.float32)
    u_c = jnp.einsum("bwd,wd->bd", conv_in.astype(jnp.float32), w)[:, None]
    u_c = jax.nn.silu(u_c)
    dA, dBx, C_t = _ssm_inputs(params, u_c)
    h = dA[:, 0] * state.h + dBx[:, 0]
    y = jnp.einsum("bdn,bn->bd", h, C_t[:, 0])[:, None]
    y = y + u_c.astype(jnp.float32) * params["d_skip"][None, None]
    y = y.astype(x.dtype) * jax.nn.silu(z)
    new_state = MambaState(conv=conv_in[:, 1:], h=h)
    if live is not None:
        new_state = MambaState(
            conv=jnp.where(live[:, None, None], new_state.conv, state.conv),
            h=jnp.where(live[:, None, None], new_state.h, state.h))
    return y @ params["w_out"].astype(x.dtype), new_state


def mamba_prefill_state(params, x, *, cfg) -> MambaState:
    """Exact post-sequence state (conv tail + ssm state) for decode handoff.

    x: (B, T, D) — the same input given to mamba_forward.
    """
    B, T, D = x.shape
    W = params["conv_w"].shape[0]
    xz = x @ params["w_in"].astype(x.dtype)
    u, _ = jnp.split(xz, 2, axis=-1)
    tail = u[:, -(W - 1):]
    if T < W - 1:
        tail = jnp.pad(u, ((0, 0), (W - 1 - T, 0), (0, 0)))
    u_c = jax.nn.silu(_conv_causal(u, params["conv_w"].astype(u.dtype)))
    dA, dBx, _ = _ssm_inputs(params, u_c)

    def step(h, inp):
        da, dbx = inp
        return da * h + dbx, None

    h0 = jnp.zeros((B, u.shape[-1], N_state(params)), jnp.float32)
    h, _ = jax.lax.scan(step, h0, (dA.swapaxes(0, 1), dBx.swapaxes(0, 1)))
    return MambaState(conv=tail.astype(jnp.float32), h=h)


def mamba_ref(params, x, *, cfg):
    """Step-by-step oracle for tests (runs decode path over the sequence)."""
    B, T, D = x.shape
    state = mamba_init_state(params, B)
    ys = []
    for t in range(T):
        y, state = mamba_step(params, x[:, t:t + 1], state, cfg=cfg)
        ys.append(y)
    return jnp.concatenate(ys, axis=1)
