"""ASYNC SUBMISSION PIPELINE DEMO — the paper's §5–6 imbalance, live.

Built on the unified ``repro.serve`` front end: one ``ServeConfig`` +
``build()`` stands up the engines, replica group, scheduler wiring, and
metrics. Sweeps open-loop offered load through live sessions and prints the
saturation/imbalance curve: below capacity the device idles (the host
can't form big batches fast enough); past capacity achieved throughput
flattens, queue wait dominates latency, and backpressure rejects.

Also contrasts the synchronous baseline with the pipelined path on the
same stream (``Server.serve`` modes — bit-identical outputs), and finishes
with a sharded-serving sweep: simulated engine replicas behind the same
admission path, scaling until the serial host prepare path saturates.

Run:  PYTHONPATH=src python examples/async_serving.py
      PYTHONPATH=src python examples/async_serving.py --smoke   # CI-sized
"""
import time

from repro.compile_cache import enable_compile_cache
from repro.serve import (OpenLoopGen, ClosedLoopGen, ServeConfig, SimServer,
                         SyntheticWorkload, build, serve, sim_requests)


def main(smoke: bool = False):
    # --smoke shrinks every sweep to CI size: same code paths, same
    # printed shape, a fraction of the wall time
    fractions = (0.5, 2.0) if smoke else (0.25, 0.5, 1.0, 2.0, 4.0)
    n_open = 32 if smoke else 64
    n_sim_batches = 12 if smoke else 32
    replica_counts = (1, 2) if smoke else (1, 2, 4)
    cfg = ServeConfig(model="llama3.2-3b", max_seq=48,
                      target_batch=8, deadline=0.01,
                      max_queue=16, policy="reject",
                      warmup=(1, 2, 4, 8))      # pre-compile bucket sizes
    srv = build(cfg)
    workload = SyntheticWorkload(vocab=srv.engine.cfg.vocab, prompt_len=6,
                                 max_new_tokens=3, seed=1)

    # capacity: service rate with full batches
    warm = workload.build(8, rid_base=10_000)
    t0 = time.perf_counter()
    srv.engine.generate_batch(warm)
    cap = 8 / (time.perf_counter() - t0)
    print(f"measured capacity ~{cap:.0f} q/s at batch 8\n")

    print("open-loop sweep (offered load vs achieved / idle / latency):")
    for frac in fractions:
        qps = cap * frac
        # request count must exceed max_queue plus the ~3 batches the
        # pipeline holds in flight, so overload can actually fill the
        # queue and trigger rejections
        sched = srv.session()
        OpenLoopGen(workload, qps=qps, n=n_open,
                    seed=int(frac * 100)).drive(sched)
        sched.result()
        rep = sched.report(offered_qps=qps)
        print(f"  {frac:4.2f}x  {rep.summary()}")

    print("\nclosed-loop (concurrency 16, always-full batches):")
    sched = srv.session(policy="block", deadline=5.0, max_queue=64)
    ClosedLoopGen(workload, concurrency=16, n=16 if smoke else 32).drive(sched)
    outs = sched.result()
    print(f"  batch sizes: {sorted({o.batch_size for o in outs})}, "
          f"{sched.report().summary()}")

    print("\nsync baseline vs pipelined (same stream, bit-identical):")
    reqs = OpenLoopGen(workload, qps=cap, n=12 if smoke else 24,
                       seed=5).requests()
    t0 = time.perf_counter()
    srv.serve(reqs, mode="sync")
    sync_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.serve(reqs, mode="pipelined")
    pipe_s = time.perf_counter() - t0
    print(f"  sync {sync_s * 1e3:.0f} ms -> pipelined {pipe_s * 1e3:.0f} ms "
          f"({sync_s / pipe_s:.2f}x)")

    print("\nsharded serving (simulated replicas, shared admission path):")
    sreqs = sim_requests(n_sim_batches * 8, max_new_tokens=4)
    for r in replica_counts:
        # one-call convenience: build -> serve -> teardown -> report
        outs, rep = serve(
            sreqs, replicas=r, target_batch=8, deadline=1.0,
            server_factory=lambda i: SimServer(host_ms_per_batch=3.0,
                                               device_ms_per_batch=8.0))
        print(f"  {r} replica(s): {rep.achieved_qps:6.0f} q/s  "
              f"(host-serial cap {1e3 / 3.0 * 8:.0f} q/s)")

    print("\ntraced run (where did the time go?):")
    tsrv = build(ServeConfig(
        replicas=2, target_batch=8, deadline=1.0, trace=True,
        server_factory=lambda i: SimServer(host_ms_per_batch=3.0,
                                           device_ms_per_batch=8.0)))
    with tsrv:
        touts = tsrv.serve(sreqs[:64], mode="pipelined")
    print(f"  {tsrv.trace_report().summary()}")
    print(f"  {tsrv.tracer.timeline(touts[0].rid)}")
    # tsrv.export_trace("trace.json") -> load in chrome://tracing
    print("done.")


if __name__ == "__main__":
    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: same code paths, smaller sweeps")
    main(smoke=ap.parse_args().smoke)
