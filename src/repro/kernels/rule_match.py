"""Pallas TPU kernel: dense interval-stabbing rule matcher (ERBIUM-on-TPU).

TPU adaptation of the NFA evaluation engine: instead of pointer-chasing a
transition graph (FPGA spatial pipeline), the rule set is a dense interval
table evaluated tile-by-tile in VMEM with a running best-(weight, index)
reduction. Layouts are criterion-major — queries (C, B), rules (C, R) — so
the minor (lane) dimension is 128-aligned for the VPU; the conjunction over
criteria is an unrolled loop of (TB, TR) compare-AND steps, which is the
MXU/VPU-friendly reformulation of the NFA's per-level transitions.

Grid: (B/TB, R/TR) with the rule dim innermost; the output block for a batch
tile is revisited across rule tiles and carries the running argmax (standard
TPU revisiting-output accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _kernel(q_ref, mn_ref, mx_ref, w_ref, bw_ref, bi_ref, *, n_crit: int,
            tile_r: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bw_ref[...] = jnp.full_like(bw_ref, -1)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    tb = q_ref.shape[1]
    tr = mn_ref.shape[1]
    acc = jnp.ones((tb, tr), jnp.bool_)
    for c in range(n_crit):  # unrolled conjunction over criteria
        qc = q_ref[c, :]                      # (TB,)
        mn, mx = mn_ref[c, :], mx_ref[c, :]   # (TR,)
        acc &= (qc[:, None] >= mn[None, :]) & (qc[:, None] <= mx[None, :])

    w = w_ref[0, :]                           # (TR,)
    score = jnp.where(acc, w[None, :], jnp.int32(-1))  # (TB, TR)
    best = jnp.max(score, axis=1)             # (TB,)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (tb, tr), 1)
    cand = jnp.where(score == best[:, None], ridx, jnp.int32(tr))
    arg = jnp.min(cand, axis=1) + j * tile_r  # global rule index, lowest-tie

    prev_w = bw_ref[0, :]
    better = best > prev_w                    # strict: earlier tile wins ties
    bw_ref[0, :] = jnp.where(better, best, prev_w)
    bi_ref[0, :] = jnp.where(better & (best >= 0), arg, bi_ref[0, :])


def rule_match_pallas(queries_t, mins_t, maxs_t, weights,
                      *, tile_b: int = 256, tile_r: int = 512,
                      interpret: bool | None = None):
    """queries_t: (C, B) int32; mins_t/maxs_t: (C, R); weights: (1, R).

    B % tile_b == 0 and R % tile_r == 0 (ops.py pads).
    Returns (best_w (1, B), best_i (1, B)).

    ``interpret=None`` compiles the kernel on a TPU and runs the Pallas
    interpreter on any other backend.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, B = queries_t.shape
    R = mins_t.shape[1]
    assert B % tile_b == 0 and R % tile_r == 0, (B, R, tile_b, tile_r)
    grid = (B // tile_b, R // tile_r)

    kern = functools.partial(_kernel, n_crit=C, tile_r=tile_r)
    out_shape = [jax.ShapeDtypeStruct((1, B), jnp.int32),
                 jax.ShapeDtypeStruct((1, B), jnp.int32)]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, tile_b), lambda i, j: (0, i)),
            pl.BlockSpec((C, tile_r), lambda i, j: (0, j)),
            pl.BlockSpec((C, tile_r), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_r), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_b), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_b), lambda i, j: (0, i)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(queries_t, mins_t, maxs_t, weights)
