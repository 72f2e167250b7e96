"""Jitted wrappers around the rule-match kernel: padding, layout transposes,
engine-lane splitting, and the partitioned (NFA-prefix-pruning analog) mode.

``match_rules`` is the public op. ``partitioned=True`` buckets queries by the
partition criterion (airport) — the dense analog of the NFA's first-level
fanout — and matches each query only against its partition's rule block plus
the wildcard block, cutting compute by ~n_partitions/skew.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as ref_mod
from repro.kernels.rule_match import rule_match_pallas


def _pad_to(x, m, axis, value):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


class DeviceRuleTable(NamedTuple):
    """Device-resident compiled rule table (criterion-major layouts)."""
    mins_t: jax.Array     # (C, Rp) int32
    maxs_t: jax.Array     # (C, Rp)
    weights: jax.Array    # (1, Rp) (-1 padding)
    decisions: jax.Array  # (Rp,)
    rule_ids: jax.Array   # (Rp,)
    n_rules: int
    # partitioned-mode blocks (optional)
    part_mins: Optional[jax.Array] = None   # (NP, Pmax, C)
    part_maxs: Optional[jax.Array] = None
    part_w: Optional[jax.Array] = None      # (NP, Pmax)
    part_rows: Optional[jax.Array] = None   # (NP, Pmax) row in dense table
    partition_col: int = 0


def device_table(table, tile_r: int = 512, partitioned: bool = False,
                 max_block: Optional[int] = None) -> DeviceRuleTable:
    """Upload a CompiledRuleTable; optionally build partition blocks."""
    mins = jnp.asarray(table.mins, jnp.int32)
    maxs = jnp.asarray(table.maxs, jnp.int32)
    w = jnp.asarray(table.weights, jnp.int32)
    mins_t = _pad_to(mins.T, tile_r, 1, 1)
    maxs_t = _pad_to(maxs.T, tile_r, 1, 0)      # min>max: never matches
    wp = _pad_to(w[None, :], tile_r, 1, -1)
    dec = _pad_to(jnp.asarray(table.decisions, jnp.int32), tile_r, 0, 0)
    rid = _pad_to(jnp.asarray(table.rule_ids, jnp.int32), tile_r, 0, -1)

    kw = {}
    if partitioned:
        NP = table.n_partitions
        counts = np.diff(table.part_offsets)
        wc = table.wildcard_rows
        pmax = int(counts.max() if len(counts) else 0) + len(wc)
        if max_block:
            pmax = min(pmax, max_block)
        pmax = max(pmax, 1)
        rows = np.full((NP, pmax), -1, np.int64)
        for p in range(NP):
            own = table.part_order[table.part_offsets[p]:
                                   table.part_offsets[p + 1]]
            blk = np.concatenate([own, wc])[:pmax]
            rows[p, :len(blk)] = blk
        valid = rows >= 0
        safe = np.where(valid, rows, 0)
        pm = table.mins[safe]
        px = table.maxs[safe]
        pw = np.where(valid, table.weights[safe], -1)
        pm = np.where(valid[..., None], pm, 1)
        px = np.where(valid[..., None], px, 0)
        kw = dict(part_mins=jnp.asarray(pm, jnp.int32),
                  part_maxs=jnp.asarray(px, jnp.int32),
                  part_w=jnp.asarray(pw, jnp.int32),
                  part_rows=jnp.asarray(safe, jnp.int32),
                  partition_col=table.partition_col)

    return DeviceRuleTable(mins_t=mins_t, maxs_t=maxs_t, weights=wp,
                           decisions=dec, rule_ids=rid,
                           n_rules=table.n_rules, **kw)


@functools.partial(jax.jit, static_argnames=("tile_b", "tile_r", "backend",
                                             "n_engines"))
def match_rules(queries, dt: DeviceRuleTable, *, tile_b: int = 256,
                tile_r: int = 512, backend: str = "pallas",
                n_engines: int = 1):
    """queries: (B, C) int32. Returns (decision, weight, rule_id) (B,) each.

    n_engines splits the batch into parallel kernel lanes (the paper's
    'NFA evaluation engines per kernel' axis) via vmap.
    """
    B, C = queries.shape
    qp = _pad_to(queries, tile_b * n_engines, 0, 0)
    Bp = qp.shape[0]

    if backend == "ref":
        w, idx = ref_mod.rule_match_ref(qp, dt.mins_t.T, dt.maxs_t.T,
                                        dt.weights[0])
    else:
        qt = qp.T  # (C, Bp)
        if n_engines > 1:
            lanes = qt.reshape(C, n_engines, Bp // n_engines).swapaxes(0, 1)
            fn = functools.partial(rule_match_pallas, tile_b=tile_b,
                                   tile_r=tile_r)
            bw, bi = jax.vmap(lambda q: fn(q, dt.mins_t, dt.maxs_t,
                                           dt.weights))(lanes)
            w = bw.reshape(Bp)
            idx = bi.reshape(Bp)
        else:
            bw, bi = rule_match_pallas(qt, dt.mins_t, dt.maxs_t, dt.weights,
                                       tile_b=tile_b, tile_r=tile_r)
            w, idx = bw[0], bi[0]

    w, idx = w[:B], idx[:B]
    safe = jnp.maximum(idx, 0)
    dec = jnp.where(idx >= 0, dt.decisions[safe], jnp.int32(-1))
    rid = jnp.where(idx >= 0, dt.rule_ids[safe], jnp.int32(-1))
    return dec, w.astype(jnp.int32), rid


@jax.jit
def match_rules_partitioned(queries, dt: DeviceRuleTable):
    """Partition-pruned matching (NFA first-level fanout analog).

    Each query gathers its airport-partition rule block (padded, wildcard
    rules appended) and matches only against it: per-query work drops from
    R to Pmax. queries: (B, C) int32.
    """
    pcol = dt.partition_col
    part = queries[:, pcol]                                # (B,) codes
    NP = dt.part_mins.shape[0]
    pid = jnp.clip(part, 0, NP - 1)
    mn = dt.part_mins[pid]                                 # (B, Pmax, C)
    mx = dt.part_maxs[pid]
    w = dt.part_w[pid]                                     # (B, Pmax)
    rows = dt.part_rows[pid]
    ok = jnp.all((queries[:, None, :] >= mn) & (queries[:, None, :] <= mx),
                 axis=-1)                                  # (B, Pmax)
    score = jnp.where(ok, w, -1)
    best = jnp.max(score, axis=1)
    # lowest dense-table row among ties (matches dense-engine tie-break)
    cand_rows = jnp.where(score == best[:, None], rows, jnp.int32(2 ** 30))
    row = jnp.min(cand_rows, axis=1)
    good = best >= 0
    safe = jnp.where(good, row, 0)
    dec = jnp.where(good, dt.decisions[safe], jnp.int32(-1))
    rid = jnp.where(good, dt.rule_ids[safe], jnp.int32(-1))
    return dec, jnp.where(good, best, -1).astype(jnp.int32), rid
