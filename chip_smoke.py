"""Bring-up smoke of the MCT-filtered serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas, one host

One process, no children. The phases, each printing one line:

1. device   -- platform, kind, count, jax version; exits non-zero unless
               JAX's first device is a TPU (there is no CPU branch);
2. cache    -- the persistent compilation cache directory in use;
3. rules    -- 160,000 MCT v2 rules (31 columns) matched on the chip by
               the compiled Pallas kernel, compared exactly with the host
               reference ``cpu_match_numpy`` (skipped with --four-chips);
4. serve    -- llama3.2-3b at full width behind ``ServeConfig``/``build``
               with the rule engine as its MCT filter: pipelined and sync
               replays plus a live session, checked against the host
               reference's verdicts; with --four-chips, four replicas
               pipelined against sync on replica 0, and nothing else.

The last line of standard output is one JSON object naming the device.
Rules, queries and weights all come from seeds. Any failed check raises,
so the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

N_RULES = 160_000
N_QUERIES = 8_192             # rules phase; also 32 requests x 256 below
N_REQUESTS, PROMPT_LEN, NEW_TOKENS, MCT_PER_REQUEST = 32, 32, 8, 256
N_LIVE = 16
# The decode path (one token per step against the KV cache) and
# model.prefill (full causal attention) compute the same last-token logits
# in bfloat16 with different reduction orders. bf16 keeps 8 significant
# bits (relative rounding up to 2**-8); over 28 residual layers those
# roundings compound to a few percent of the logit scale, so the bound is
# 5% of the largest reference logit.
LOGIT_RTOL = 0.05


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def phase_device():
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{d.platform!r}")
    return devs


def phase_cache() -> None:
    from repro.compile_cache import enable_compile_cache
    print(f"[cache] dir={enable_compile_cache()}", flush=True)


def build_rules():
    from repro.core.compiler import compile_rules
    from repro.core.engine import ErbiumEngine
    from repro.core.rules import generate_rules
    t0 = time.perf_counter()
    ruleset = generate_rules(N_RULES, version=2, seed=0)
    engine = ErbiumEngine(compile_rules(ruleset))
    return ruleset, engine, time.perf_counter() - t0


def phase_rules(ruleset, engine, build_s: float) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import cpu_match_numpy
    from repro.core.rules import generate_queries
    from repro.kernels import ops
    table = engine.table
    enc = engine.encode_queries_host(
        generate_queries(ruleset, N_QUERIES, seed=1))
    t0 = time.perf_counter()
    got = [np.asarray(x) for x in engine.match(enc)]
    match_s = time.perf_counter() - t0
    want = cpu_match_numpy(table, enc)
    bad = np.zeros(len(enc), bool)
    for g, w in zip(got, want):
        bad |= g != w
    text = ops.match_rules.lower(
        jnp.asarray(enc), engine.dt, tile_b=engine.tile_b,
        tile_r=engine.tile_r, backend=engine.backend,
        n_engines=engine.n_engines).as_text()
    custom = "tpu_custom_call" in text
    print(f"[rules] rules={table.n_rules} cols={table.n_cols} "
          f"padded={engine.dt.mins_t.shape[1]} queries={len(enc)} "
          f"matched={int((want[0] >= 0).sum())} "
          f"mismatches={int(bad.sum())} tpu_custom_call={custom} "
          f"build_s={build_s:.1f} first_match_s={match_s:.2f}", flush=True)
    check(not bad.any(), f"{int(bad.sum())} kernel/host mismatches")
    check(custom, "the matcher did not lower to a compiled TPU kernel")


class DeviceLog:
    """Which devices each replica's MCT match and decode steps ran on,
    keyed by the device ``execute_prepared`` was given (None = default)."""

    def __init__(self, server, engine):
        self.seen = {}
        self._lock = threading.Lock()
        self._local = threading.local()
        execute, match, decode = (server.execute_prepared, engine.match,
                                  server._decode)

        def note(kind, arr):
            with self._lock:
                self.seen.setdefault(self._local.device, {}).setdefault(
                    kind, set()).update(arr.devices())

        def logged_execute(pb, *, device=None):
            self._local.device = device
            return execute(pb, device=device)

        def logged_match(encoded, device=None):
            out = match(encoded, device=device)
            note("match", out[0])
            return out

        def logged_decode(*args):
            logits, cache = decode(*args)
            note("decode", logits)
            return logits, cache

        server.execute_prepared = logged_execute
        engine.match = logged_match
        server._decode = logged_decode


def host_verdicts(engine, reqs):
    """Make every fourth request infeasible (one connection a minute short
    of its MCT, per the host reference) and return the rids that must be
    answered."""
    import numpy as np

    from repro.core.engine import cpu_match_numpy
    table = engine.table
    enc = engine.encode_queries_host(
        [q for r in reqs for q in r.mct_queries])
    dec, _, _ = cpu_match_numpy(table, enc)
    mct = np.where(dec >= 0, dec, table.default_decision).reshape(
        len(reqs), MCT_PER_REQUEST)
    for i, r in enumerate(reqs):
        if i % 4 == 0:
            r.connect_minutes[0] = int(mct[i, 0]) - 1
    return {r.rid for i, r in enumerate(reqs)
            if all(c >= m for c, m in zip(r.connect_minutes, mct[i]))}


def logits_agreement(srv, reqs) -> None:
    """Last-token logits of one batch: the decode-path prefill that serving
    runs against model.prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    eng = srv.engine
    toks = np.stack([r.tokens for r in reqs[:srv.cfg.target_batch]])
    cache = eng.model.init_cache(len(toks), eng.max_seq)
    for pos in range(toks.shape[1]):
        logits, cache = eng._decode(eng.params, cache,
                                    jnp.asarray(toks[:, pos:pos + 1]),
                                    jnp.int32(pos))
    ref, _ = jax.jit(eng.model.prefill)(eng.params,
                                        {"tokens": jnp.asarray(toks)})
    a = np.asarray(logits[:, -1], np.float32)
    b = np.asarray(ref[:, -1], np.float32)
    finite = bool(np.isfinite(a).all() and np.isfinite(b).all())
    diff, scale = float(np.abs(a - b).max()), float(np.abs(b).max())
    top1 = int((a.argmax(-1) == b.argmax(-1)).sum())
    print(f"[logits] batch={len(toks)} finite={finite} max_abs_diff={diff} "
          f"max_abs_ref={scale} bound={LOGIT_RTOL * scale} "
          f"top1_agree={top1}/{len(toks)}", flush=True)
    check(finite, "non-finite logits")
    check(diff <= LOGIT_RTOL * scale,
          f"decode-path logits differ from prefill by {diff}")


def phase_serve(ruleset, engine, devices, four_chips: bool) -> None:
    import numpy as np

    from repro.serve import ServeConfig, build
    from repro.serve.loadgen import SyntheticWorkload
    t0 = time.perf_counter()
    srv = build(ServeConfig(
        model="llama3.2-3b", reduced=False, rule_filter=engine,
        max_seq=128, target_batch=8, warmup=(8,),
        devices=devices[:4] if four_chips else None))
    build_s = time.perf_counter() - t0
    eng, mc = srv.engine, srv.engine.cfg
    stats = devices[0].memory_stats() or {}
    print(f"[serve] model={mc.arch} d_model={mc.d_model} "
          f"layers={mc.n_layers} vocab={mc.vocab} "
          f"replicas={len(srv.group.replicas)} build_s={build_s:.1f} "
          f"hbm_peak_bytes={stats.get('peak_bytes_in_use')} "
          f"hbm_limit_bytes={stats.get('bytes_limit')}", flush=True)

    reqs = SyntheticWorkload(vocab=mc.vocab, prompt_len=PROMPT_LEN,
                             max_new_tokens=NEW_TOKENS,
                             n_mct_queries=MCT_PER_REQUEST, ruleset=ruleset,
                             seed=0).build(N_REQUESTS)
    expect = host_verdicts(engine, reqs)
    if not four_chips:
        logits_agreement(srv, reqs)
    log = DeviceLog(eng, engine)

    t0 = time.perf_counter()
    pipe = {c.rid: c for c in srv.serve(reqs, mode="pipelined")}
    pipe_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sync = {c.rid: c for c in srv.serve(reqs, mode="sync")}
    sync_s = time.perf_counter() - t0
    same = sum(np.array_equal(pipe[r].tokens, sync[r].tokens)
               for r in pipe.keys() & sync.keys())
    runs = {"pipelined": pipe, "sync": sync}
    if not four_chips:
        sess = srv.session()
        for r in reqs[:N_LIVE]:
            check(sess.submit(r), f"live session rejected rid {r.rid}")
        runs["live"] = {c.rid: c for c in sess.result()}
    full = {name: sum(len(c.tokens) == NEW_TOKENS and not c.truncated
                      for c in out.values())
            for name, out in runs.items()}
    print(f"[serve] requests={len(reqs)} "
          f"mct_queries={len(reqs) * MCT_PER_REQUEST} "
          f"expected_answered={len(expect)} "
          + " ".join(f"{n}_answered={len(o)}" for n, o in runs.items())
          + f" pipelined_eq_sync={same}/{len(sync)} "
          + " ".join(f"{n}_full={k}" for n, k in full.items())
          + f" pipelined_s={pipe_s:.2f} sync_s={sync_s:.2f}", flush=True)

    check(set(pipe) == expect, "pipelined answered rids differ from the "
          "host reference")
    check(set(sync) == expect, "sync answered rids differ from the host "
          "reference")
    check(same == len(expect), "pipelined tokens differ from sync")
    if "live" in runs:
        live_expect = expect & {r.rid for r in reqs[:N_LIVE]}
        check(set(runs["live"]) == live_expect, "live session answered "
              "rids differ from the host reference")
    for name, out in runs.items():
        check(full[name] == len(out), f"{name}: a completion lacks its "
              f"{NEW_TOKENS} tokens")
        check(all(0 <= t < mc.vocab for c in out.values() for t in c.tokens),
              f"{name}: token id out of range")

    want = {None: devices[0]}           # sync, and the one-chip replica
    if four_chips:
        want.update({d: d for d in devices[:4]})
    for dev, kinds in sorted(log.seen.items(), key=lambda kv: str(kv[0])):
        print(f"[replica] device={dev} "
              + " ".join(f"{k}_on={sorted(str(d) for d in v)}"
                         for k, v in sorted(kinds.items())), flush=True)
    for dev, on in want.items():
        kinds = log.seen.get(dev, {})
        check(kinds.get("match") == {on} and kinds.get("decode") == {on},
              f"replica on {dev}: ran on {kinds}, not on {on}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="four one-chip replicas behind the router, "
                         "pipelined against sync; no other phase")
    args = ap.parse_args(argv)
    devices = phase_device()
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, "
              f"found {len(devices)}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    phase_cache()
    ruleset, engine, build_s = build_rules()
    if not args.four_chips:
        phase_rules(ruleset, engine, build_s)
    phase_serve(ruleset, engine, devices, args.four_chips)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
