"""ERBIUM engine (online side): Host-Executor + FPGA-kernel analog.

``ErbiumEngine`` owns the device-resident rule table and exposes batched
matching; ``n_engines`` reproduces the paper's 'NFA evaluation engines per
kernel' axis (parallel lanes over a batch), ``n_kernels`` the kernels-per-
accelerator axis (independent engines with their own table replica).

Rule hot-reload (the paper's 500 µs NFA update) swaps the device table
buffers without touching the compiled matcher.

``match`` pads its host rows, on the host, to the rows the kernel runs
on: their count rounded up to the batch tile (``tile_b * n_engines``).
So the jitted match compiles once per bucket of that tile, not once per
row count, and the kernel's work is what it was. The answers are cut back
to one per row on the host, between the copy back and a copy onto the
device again: slicing a device array compiles once per length. The
padded rows' host-to-device copy runs inside a ``mct.dispatch`` profiler
span, and its time is what ``last_dispatch_s`` reports to the caller's
thread. A jitted match call that compiles runs inside a ``mct.compile``
span, and its time is what ``last_compile_s`` reports. Whether a call
compiles is read from the jit's own cache key (the argument shapes, dtypes
and placements, the table's structure, the static arguments): a key that
no call has returned from yet compiles, or waits on the worker that
compiles it.

CPU baselines (paper §5.2): ``cpu_match_numpy`` — the optimised vectorised
implementation standing in for the refactored C++ MCT v2 module; and
``cpu_match_python`` — a per-query scalar loop (the pre-optimisation shape).
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.compiler import CompiledRuleTable, compile_rules
from repro.core.encoder import encode, queries_to_arrays
from repro.core.rules import RuleSet
from repro.kernels import ops
from repro.serve.trace import EXECUTOR_SPANS

# jit cache keys of the match calls that have returned, process-wide as the
# jit cache is; a key joins only once its call has returned, so workers
# racing on one new key are all labelled as compiling
_returned: set = set()


def _span(stage: str):
    return jax.profiler.TraceAnnotation(EXECUTOR_SPANS[stage])


def _jit_key(fn, args, static) -> tuple:
    leaves, tree = jax.tree_util.tree_flatten(args)
    return (fn, tree, static, tuple(
        (x.shape, x.dtype, x.sharding) if isinstance(x, jax.Array)
        else type(x) for x in leaves))


class ErbiumEngine:
    def __init__(self, table: CompiledRuleTable, *, n_engines: int = 1,
                 tile_b: int = 256, tile_r: int = 512,
                 backend: str = "pallas", partitioned: bool = False):
        self.table = table
        self.n_engines = n_engines
        self.tile_b, self.tile_r = tile_b, tile_r
        self.backend = backend
        self.partitioned = partitioned
        self.dt = ops.device_table(table, tile_r=tile_r,
                                   partitioned=partitioned)
        # one copy of the table per device a replica matches on, so a
        # replica pinned to device d never matches on the default device
        self._dev_dt: Dict[object, ops.DeviceRuleTable] = {}
        self.reload_us: Optional[float] = None
        self._calls = threading.local()

    # -- online path ---------------------------------------------------------
    def encode(self, fields: Dict[str, np.ndarray]) -> np.ndarray:
        return encode(self.table, fields)

    def _table_on(self, device) -> ops.DeviceRuleTable:
        """The device table, copied once to ``device`` (None = default)."""
        dt = self.dt
        if device is None:
            return dt
        if device not in self._dev_dt:
            self._dev_dt[device] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, device)
                if isinstance(x, jax.Array) else x, dt)
        return self._dev_dt[device]

    def match(self, encoded, device=None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(decision, weight, rule_id) of the (B, C) host rows ``encoded``,
        each (B,), computed on ``device`` (None = the default device)."""
        dt = self._table_on(device)
        n = len(encoded)
        t0 = time.perf_counter()
        with _span("dispatch"):
            q = np.pad(np.asarray(encoded, np.int32),
                       ((0, -n % (self.tile_b * self.n_engines)), (0, 0)))
            q = jax.device_put(q, device)
            q.block_until_ready()
        t1 = time.perf_counter()
        self._calls.dispatch_s = t1 - t0
        if self.partitioned:
            fn, static = ops.match_rules_partitioned, {}
        else:
            fn, static = ops.match_rules, dict(
                tile_b=self.tile_b, tile_r=self.tile_r,
                backend=self.backend, n_engines=self.n_engines)
        key = _jit_key(fn, (q, dt), tuple(static.items()))
        compiles = key not in _returned
        with _span("compile") if compiles else nullcontext():
            out = fn(q, dt, **static)
        self._calls.compile_s = time.perf_counter() - t1 if compiles else 0.0
        if compiles:
            _returned.add(key)
        # cut on the host: slicing the device arrays compiles per length
        return jax.device_put(
            tuple(x[:n] for x in jax.device_get(out)), device)

    def last_dispatch_s(self) -> float:
        """Seconds the calling thread's last ``match`` spent padding its
        rows and copying them to the device."""
        return getattr(self._calls, "dispatch_s", 0.0)

    def last_compile_s(self) -> float:
        """Seconds the calling thread's last ``match`` spent in a jitted
        call that compiled; 0 if it did not compile."""
        return getattr(self._calls, "compile_s", 0.0)

    def encode_queries_host(self, queries: Sequence[Dict[str, int]]
                            ) -> np.ndarray:
        """Host-side half of the online path: raw query dicts -> dense
        (B, C) int32 kernel input. Pure numpy — the async scheduler runs
        this for batch N+1 while the device executes batch N."""
        return self.encode(queries_to_arrays(list(queries)))

    def match_queries(self, queries: Sequence[Dict[str, int]]):
        return self.match(self.encode_queries_host(queries))

    # -- rule update (hot reload) --------------------------------------------
    def reload(self, ruleset: RuleSet) -> float:
        """Swap in a new rule set; returns device-swap time in µs (the
        analog of the paper's 500 µs NFA reload; compilation is offline)."""
        table = compile_rules(ruleset)
        t0 = time.perf_counter()
        dt = ops.device_table(table, tile_r=self.tile_r,
                              partitioned=self.partitioned)
        jax.block_until_ready(dt.mins_t)
        us = (time.perf_counter() - t0) * 1e6
        self.table, self.dt, self.reload_us = table, dt, us
        self._dev_dt = {}
        return us


# ---------------------------------------------------------------------------
# CPU baselines
# ---------------------------------------------------------------------------


def cpu_match_numpy(table: CompiledRuleTable, encoded: np.ndarray,
                    block: int = 1024):
    """Optimised vectorised CPU implementation (the refactored-C++ stand-in).

    Prunes like the software module: per block of queries, the criteria
    that reject most (measured on a sample of query/rule pairs) are
    evaluated densely until few (query, rule) pairs survive; the remaining
    criteria then check only those pairs. Ties on weight go to the lowest
    rule row, as in the kernel."""
    B = encoded.shape[0]
    R = table.n_rules
    dec = np.full((B,), -1, np.int32)
    wgt = np.full((B,), -1, np.int32)
    rid = np.full((B,), -1, np.int32)
    mins_t = np.ascontiguousarray(table.mins.T)      # (C, R)
    maxs_t = np.ascontiguousarray(table.maxs.T)
    w = table.weights.astype(np.int64)
    sample = slice(0, R, max(1, R // 2048))
    for s in range(0, B, block):
        q = encoded[s:s + block]
        rate = ((q[:64, :, None] >= mins_t[None, :, sample])
                & (q[:64, :, None] <= maxs_t[None, :, sample])
                ).mean(axis=(0, 2))
        order = np.argsort(rate, kind="stable")
        ok = np.ones((len(q), R), bool)
        k = 0
        while k < len(order) and ok.mean() > 1 / 64:
            c = order[k]
            ok &= (q[:, c:c + 1] >= mins_t[c]) & (q[:, c:c + 1] <= maxs_t[c])
            k += 1
        qi, r = np.nonzero(ok)
        for c in order[k:]:
            v = q[qi, c]
            keep = (v >= mins_t[c, r]) & (v <= maxs_t[c, r])
            qi, r = qi[keep], r[keep]
        # best weight, then lowest row: one int64 key per surviving pair
        key = np.full(len(q), -1, np.int64)
        np.maximum.at(key, qi, w[r] * R + (R - 1 - r))
        good = key >= 0
        idx = np.where(good, R - 1 - key % R, 0)
        dec[s:s + block] = np.where(good, table.decisions[idx], -1)
        wgt[s:s + block] = np.where(good, key // R, -1)
        rid[s:s + block] = np.where(good, table.rule_ids[idx], -1)
    return dec, wgt, rid


def cpu_match_python(table: CompiledRuleTable, encoded: np.ndarray,
                     limit: Optional[int] = None):
    """Naive per-query scalar loop (pre-optimisation baseline)."""
    B = encoded.shape[0] if limit is None else min(limit, encoded.shape[0])
    mins, maxs, w = table.mins, table.maxs, table.weights
    out = np.full((B, 3), -1, np.int64)
    for i in range(B):
        q = encoded[i]
        best_w, best_r = -1, -1
        for r in range(mins.shape[0]):
            okr = True
            for c in range(mins.shape[1]):
                v = q[c]
                if v < mins[r, c] or v > maxs[r, c]:
                    okr = False
                    break
            if okr and w[r] > best_w:
                best_w, best_r = int(w[r]), r
        if best_r >= 0:
            out[i] = (table.decisions[best_r], best_w,
                      table.rule_ids[best_r])
    return out[:, 0], out[:, 1], out[:, 2]
