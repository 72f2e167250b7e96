"""Readings that set the benchmark's numbers, many runs in one process.

    # the program's and the control's readings on several seeds
    python3 bench/calibrate.py --workload mct-v2-160k.de_closed \\
        --seconds 10 --seeds 1,2,3 --control
    # a cell that BENCHMARK.json does not hold, named <config>.<traffic>,
    # with a changed mix (any key of the traffic file)
    python3 bench/calibrate.py --workload hymba-1.5b.de_steady \\
        --seeds 1 --set 'prompt_tokens=[16,16]' --diagnose
    # the rate sweep that fixes an open-loop serving cell's rate
    python3 bench/calibrate.py --workload hymba-1.5b.de_steady \\
        --seconds 20 --rates 2,4,6,8

Prints one JSON line per run. ``--dump-trace DIR`` keeps the profiler's
raw trace of a ``--trace`` run there, with a summary of its planes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def sweep_line(run, rate: float) -> dict:
    """How a run at ``rate`` kept up: answered rate in the window, latency
    tails, and whether latency grew from the first to the last third."""
    lat = run.latencies_ms()
    order = sorted(run.due, key=run.due.get)
    third = max(1, len(order) // 3)
    by = {rid: (run.verdict.get(rid, (run.drain_end,))[0] - run.due[rid])
          * 1e3 for rid in order}
    return {"rate": rate, "offered": len(run.due),
            "answered_per_s": len(run.answered_in_window()) / run.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_third_p50_ms": float(np.median([by[r] for r in
                                                   order[:third]])),
            "last_third_p50_ms": float(np.median([by[r] for r in
                                                  order[-third:]])),
            "drain_s": run.drain_end - run.t1,
            "batches": len(run.report.batch_sizes),
            "mean_batch": float(np.mean(run.report.batch_sizes or [0])),
            "compiles": run.compiles, "rejected": len(run.rejected)}


def diagnose(run) -> dict:
    """The worst served token seen through a second path of the program:
    its full-sequence bfloat16 forward (``Model.logits``) over the same
    tokens. If that path puts the served token first too, the gap comes
    from bfloat16 arithmetic and not from the serving path."""
    import jax
    import jax.numpy as jnp
    from bench import lm_ref, program
    from repro.models.registry import build_model
    w = run.gaps["worst"]
    if w is None:
        return {}
    q = run.traffic.queries[run.query_of[w["rid"]]]
    toks = np.concatenate([q.prompt, np.asarray(
        run.completions[w["rid"]].tokens[:-1], np.int32)])[None]
    model = build_model(program.model_config(run.cfg))
    full = np.asarray(jax.jit(model.logits)(
        run.params, {"tokens": jnp.asarray(toks)})[0, w["position"]],
        np.float32)
    ref = lm_ref.logits_at(run.params, toks,
                           np.asarray([[w["position"]]], np.int32),
                           run.cfg)[0, 0]
    top = np.argsort(-ref)[:4]
    return dict(w, prefill_path_token=int(full.argmax()),
                prefill_path_gap=float(ref.max() - ref[full.argmax()]),
                ref_top4=[[int(t), float(ref[t])] for t in top],
                prefill_path_top4=[[int(t), float(full[t])]
                                   for t in np.argsort(-full)[:4]])


def describe_trace(log_dir: str) -> dict:
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            ev = list(line.events)
            lines[line.name] = {
                "n": len(ev),
                "first": [[e.name, e.start_ns, e.duration_ns,
                           {k: str(v) for k, v in e.stats}]
                          for e in ev[:4]]}
        out[plane.name] = lines
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--dump-trace", default=None)
    ap.add_argument("--diagnose", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness
    from repro.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    override = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        override[k] = json.loads(v)
    spec = harness.load_spec(ROOT)
    if args.workload not in {w["name"] for w in spec["workloads"]}:
        config, mix_name = args.workload.rsplit(".", 1)
        spec["workloads"].append({"name": args.workload, "config": config,
                                  "traffic": mix_name, "chips": 1})
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else []
    plan = [(seeds[i % len(seeds)], r) for i, r in enumerate(rates)] \
        if rates else [(s, None) for s in seeds]
    t_start = T_START
    for seed, rate in plan:
        mix = dict(override)
        if rate is not None:
            mix["rate_per_s"] = rate
        runs: list = []
        t0 = time.perf_counter()
        res = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                               args.trace, t_start=t_start, spec=spec,
                               control=args.control, mix_override=mix,
                               keep_trace_dir=args.dump_trace,
                               runs_out=runs)
        line = {"seed": seed, "correct": res["correct"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "checks": {k: v["value"] for k, v in res["checks"].items()},
                "log": res["_log"], "device": res["device"],
                "control": res.get("control"),
                "run_s": time.perf_counter() - t0}
        if rate is not None:
            line["sweep"] = sweep_line(runs[0], rate)
        if args.diagnose:
            line["diagnose"] = diagnose(runs[0])
        if "breakdown" in res:
            line["breakdown"] = res["breakdown"]
        print(json.dumps(line), flush=True)
        if args.dump_trace:
            import glob
            import os
            Path(args.dump_trace, "planes.json").write_text(
                json.dumps(describe_trace(args.dump_trace), indent=1))
            tr = runs[0].trace
            tr = dict(tr, host=tr["host"][:20000], devices=[
                dict(d, ops=d["ops"][:20000]) for d in tr["devices"]])
            Path(args.dump_trace, "reduced.json").write_text(json.dumps(tr))
            for p in glob.glob(f"{args.dump_trace}/**/*.xplane.pb",
                               recursive=True):
                os.remove(p)
        t_start = time.perf_counter()
        del runs
    return 0


if __name__ == "__main__":
    sys.exit(main())
