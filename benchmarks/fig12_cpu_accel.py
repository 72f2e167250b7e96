"""Paper Fig. 12: per-user-query execution time, optimised CPU baseline vs
the accelerated engine, as a function of MCT queries checked; plus the
number of accelerator calls under the paper's batching policy.

Reproduced phenomenon: CPU wins below a crossover workload (paper: ~400
queries); the engine wins above it even when called multiple times.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, rule_system
from repro.core.aggregator import paper_policy
from repro.core.encoder import encode_queries
from repro.core.engine import ErbiumEngine, cpu_match_numpy
from repro.core.workload import generate_workload
from repro.kernels import ops


def run():
    rs, table, qs, enc = rule_system(2)
    # accelerated path = the partition-pruned engine (the NFA-fanout
    # advantage ERBIUM gets in hardware, here measured for real); the CPU
    # baseline is the optimised vectorised full scan. Interpret-mode Pallas
    # is a correctness harness, not a timing proxy (see README).
    eng = ErbiumEngine(table, partitioned=True)
    wl = generate_workload(rs, 10, seed=7, mean_ts=400.0)
    # warmup compile
    eng.match(enc[:256])

    rows = []
    for uq in sorted(wl, key=lambda u: u.n_mct):
        batches = paper_policy(uq)
        if not batches:
            continue
        encs = [encode_queries(table, b.queries) for b in batches]
        for e in encs:  # warm the jit caches per shape
            jax.block_until_ready(eng.match(e))
        t0 = time.perf_counter()
        for e in encs:
            cpu_match_numpy(table, e)
        t_cpu = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        for e in encs:
            jax.block_until_ready(eng.match(e))
        t_acc = (time.perf_counter() - t0) * 1e6
        n = uq.n_mct
        rows.append((n, t_cpu, t_acc, len(batches)))
        emit(f"fig12/uq_mct{n}", t_acc,
             f"cpu_us={t_cpu:.0f};accel_calls={len(batches)};"
             f"speedup={t_cpu / max(t_acc, 1):.2f}")
    big = [r for r in rows if r[0] >= 400]
    if big:
        sp = np.mean([r[1] / r[2] for r in big])
        emit("fig12/speedup_above_400q", 0.0,
             f"mean={sp:.2f} (paper: accel wins above ~400 queries)")
    return rows


if __name__ == "__main__":
    run()
