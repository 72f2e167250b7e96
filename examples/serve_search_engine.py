"""END-TO-END DRIVER — a miniature flight-search serving stack, the paper's
architecture on one box:

  Injector (replayed workload)
    -> Domain Explorer (user query -> Travel Solutions -> MCT queries)
    -> DeadlineAggregator (batch formation, the paper's §5 lesson)
    -> MCT Wrapper (workers) -> ERBIUM rule engine   [connection filtering]
    -> LM route scorer (assigned arch, reduced)      [Fig 14 co-location]

Run:  PYTHONPATH=src python examples/serve_search_engine.py
"""
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.aggregator import batch_stats, paper_policy
from repro.core.compiler import compile_rules
from repro.core.engine import ErbiumEngine
from repro.core.rules import generate_rules
from repro.core.workload import generate_workload, workload_stats
from repro.core.wrapper import MCTWrapper
from repro.serve import Request, serve


def main():
    # offline: rules + engine
    ruleset = generate_rules(2_000, version=2, seed=0)
    table = compile_rules(ruleset)
    engine = ErbiumEngine(table, tile_b=256, tile_r=512)

    # injector: replay a production-shaped trace
    wl = generate_workload(ruleset, 8, seed=3, mean_ts=120.0)
    print("workload:", workload_stats(wl))

    # MCT stage: wrapper with 2 workers, paper batching policy
    wrap = MCTWrapper([engine], n_workers=2)
    wrap.start()
    t0 = time.perf_counter()
    n_batches = 0
    batches_per_uq = {}
    for uq in wl:
        bs = paper_policy(uq)
        batches_per_uq[uq.uid] = bs
        for b in bs:
            wrap.submit(b)
            n_batches += 1
    results = wrap.drain(n_batches)
    wrap.stop()
    mct_s = time.perf_counter() - t0
    total_q = sum(len(r.decisions) for r in results)
    print(f"MCT stage: {total_q} queries in {n_batches} batches "
          f"({batch_stats([b for bs in batches_per_uq.values() for b in bs])})"
          f" -> {total_q / mct_s:.0f} q/s end-to-end")

    # route scoring stage: LM server scores surviving routes behind the
    # unified repro.serve front end — host encode of batch N+1 overlapped
    # with device execution of batch N (see examples/async_serving.py for
    # the full offered-load and replica sweeps)
    from repro.configs.base import get_config
    vocab = get_config("llama3.2-3b").reduced().vocab
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    tokens=rng.integers(1, vocab, 8).astype(np.int32),
                    max_new_tokens=4, arrival=i * 0.002)
            for i in range(12)]
    outs, rep = serve(reqs, model="llama3.2-3b", max_seq=64,
                      target_batch=4, deadline=0.01, warmup=(4,))
    sizes = [o.batch_size for o in outs]
    print(f"route scoring: {len(outs)} requests served, batch sizes {sizes}")
    print(f"  prefill {np.mean([o.prefill_ms for o in outs]):.1f} ms, "
          f"decode {np.mean([o.decode_ms for o in outs]):.1f} ms (batched)")
    print(f"  {rep.summary()}")
    print("done.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
