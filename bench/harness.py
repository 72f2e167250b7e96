"""Run one cell once: set up, measure a window, check, report.

``run_cell`` is the whole run apart from the look for a chip, which
``run.py`` makes first. Set-up makes the rules, the traffic and, for a
configuration that names a model, the weights from the seed, and
compiles the rules with the program's compiler. A configuration with a
model is served through ``ServeConfig``/``build`` (the program's own
warm-up compiles the decode step for the cell's power-of-two batches)
and a live ``Server.session()``; one without is the MCT engine behind
the program's host executor, ``MCTWrapper``, which takes one batch of
checks per user query. The window offers the traffic for ``seconds``:
an open loop submits each user query when it is due, a closed loop
submits a client's next query when its last one is answered.
Compiles inside the window neither read nor write the persistent cache,
so every run pays the same for them; they are counted. After the window
the session drains, the device's peak memory is read, the program's state
is freed, and what the window produced is compared with the reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import flops, lm_ref, mct, program, trace_reduce, traffic

BENCH_DIR = Path(__file__).resolve().parent
TRACE_SPAN = "bench.window"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(bench_dir: Path, name: str) -> dict:
    return json.loads((bench_dir / "configs" / f"{name}.json").read_text())


def load_mix(bench_dir: Path, name: str) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def load_reader(bench_dir: Path, metric: str):
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks_for(bench_dir: Path, device_kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng([seed, 7]).integers(0, 2**31 - 1))


# ---------------------------------------------------------------------------
# what a run leaves for the readers
# ---------------------------------------------------------------------------

@dataclass
class Run:
    cell: str
    seed: int
    cfg: dict
    mix: dict
    seconds: float
    bench_dir: Path
    device_kind: str = ""
    setup_s: float = 0.0
    t0: float = 0.0                       # window start (perf_counter)
    due: Dict[int, float] = field(default_factory=dict)
    verdict: Dict[int, tuple] = field(default_factory=dict)  # rid->(t, kind)
    rejected: List[int] = field(default_factory=list)
    lateness_s: List[float] = field(default_factory=list)
    completions: Dict[int, object] = field(default_factory=dict)
    query_of: Dict[int, int] = field(default_factory=dict)   # rid -> uid
    report: object = None
    taps: object = None
    compiles: int = 0
    trace: Optional[dict] = None
    modules: Dict[str, str] = field(default_factory=dict)
    table_shape: tuple = (0, 0)           # (columns, padded rules)
    traffic: object = None
    drain_end: float = 0.0
    gaps: Optional[dict] = None           # per-token logit gaps, checked
    answers: Dict[int, object] = field(default_factory=dict)  # wrapper's

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def peaks(self) -> dict:
        return peaks_for(self.bench_dir, self.device_kind)

    def latencies_ms(self) -> List[float]:
        """Due-to-verdict time of every query due in the window; a query
        with no verdict counts until the drain ended."""
        out = []
        for rid, due in self.due.items():
            t = self.verdict.get(rid, (self.drain_end, "none"))[0]
            out.append((t - due) * 1e3)
        return out

    def answered_in_window(self) -> List[int]:
        return [rid for rid, (t, kind) in self.verdict.items()
                if self.t0 <= t <= self.t1]

    def served_flops(self, in_window: bool = True) -> float:
        tot = 0.0
        for rid, c in self.completions.items():
            t = self.verdict.get(rid, (math.inf,))[0]
            if in_window and not (self.t0 <= t <= self.t1):
                continue
            q = self.traffic.queries[self.query_of[rid]]
            tot += flops.sequence_flops(self.cfg, len(q.prompt),
                                        len(c.tokens))
        return tot


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class _CompileCounter:
    """Counts XLA backend compiles while ``on``; a listener on JAX's
    monitoring events, removed by ``close``."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_):
        if self.on and name == COMPILE_EVENT:
            self.n += 1

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._event)


def _persistent_cache(enabled: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


class _Tracer:
    """Profiles the second half of the window (at most 10 s) in a thread
    of its own; the trace goes to a temporary directory and is reduced
    after the window."""

    def __init__(self, t0: float, seconds: float,
                 keep_dir: Optional[str] = None):
        self.start = t0 + seconds - min(seconds / 2, 10.0)
        self.stop = t0 + seconds
        self.keep = keep_dir is not None
        self.dir = keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        import jax
        try:
            time.sleep(max(0.0, self.start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(TRACE_SPAN):
                time.sleep(max(0.0, self.stop - time.perf_counter()))
            jax.profiler.stop_trace()
        except BaseException as e:          # surfaced by result()
            self.error = e

    def result(self) -> dict:
        self._thread.join()
        try:
            if self.error is not None:
                raise RuntimeError("profiling failed") from self.error
            return trace_reduce.bench_window(trace_reduce.load(self.dir),
                                             TRACE_SPAN)
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def _drive(run: Run, sess, reqs: list) -> None:
    """Offer the traffic for ``run.seconds``; returns once the window has
    closed (the session still drains)."""
    lock = threading.Lock()
    clock = time.perf_counter
    mix = run.mix

    def answered(c):
        run.verdict[c.rid] = (clock(), "scored")
        run.completions[c.rid] = c
        release()

    def filtered(rid):
        run.verdict[rid] = (clock(), "filtered")
        release()

    pool = {"next": 0, "open": True}

    def submit_next() -> None:
        # under the lock, so that no submit races the session's close
        with lock:
            i = pool["next"]
            if not pool["open"] or i >= len(reqs):
                return
            pool["next"] = i + 1
            r = reqs[i]
            run.due[r.rid] = clock()
            run.query_of[r.rid] = i
            if not sess.submit(r):
                run.rejected.append(r.rid)

    def release():
        if mix["loop"] == "closed" and clock() < run.t1:
            submit_next()

    sess.on_complete = answered
    sess.on_drop = filtered
    run.t0 = clock() + 0.01
    if mix["loop"] == "open":
        due = run.t0 + run.traffic.offsets_s
        for i, r in enumerate(reqs):
            wait = due[i] - clock()
            if wait > 0:
                time.sleep(wait)
            now = clock()
            run.lateness_s.append(now - due[i])
            run.due[r.rid] = due[i]
            run.query_of[r.rid] = i
            if not sess.submit(r):
                run.rejected.append(r.rid)
    else:
        time.sleep(max(0.0, run.t0 - clock()))
        for _ in range(int(mix["clients"])):
            submit_next()
    time.sleep(max(0.0, run.t1 - clock()))
    with lock:
        pool["open"] = False


def _drive_wrapper(run: Run, wrapper, batches: list,
                   drain_s: float = 60.0) -> None:
    """Offer the traffic to the MCT wrapper for ``run.seconds``, then wait
    for every answer, at most ``drain_s`` past the close."""
    import queue
    clock = time.perf_counter
    lock = threading.Lock()
    closed = run.mix["loop"] == "closed"
    state = {"next": 0, "open": True, "out": 0}

    def submit(b, due):
        run.due[b.uid] = due
        run.query_of[b.uid] = b.uid
        state["out"] += 1
        wrapper.submit(b)

    def submit_next() -> None:
        with lock:
            i = state["next"]
            if state["open"] and i < len(batches):
                state["next"] = i + 1
                submit(batches[i], clock())

    def collect() -> None:
        while True:
            with lock:
                if not state["open"] and state["out"] == 0:
                    return
            try:
                (res,) = wrapper.drain(1, timeout=0.2)
            except queue.Empty:
                if clock() > run.t1 + drain_s:
                    return
                continue
            t = clock()
            run.verdict[res.uid] = (t, "answered")
            run.answers[res.uid] = res
            with lock:
                state["out"] -= 1
            if closed and t < run.t1:
                submit_next()

    collector = threading.Thread(target=collect, daemon=True)
    run.t0 = clock() + 0.01
    collector.start()
    if closed:
        time.sleep(max(0.0, run.t0 - clock()))
        for _ in range(int(run.mix["clients"])):
            submit_next()
    else:
        due = run.t0 + run.traffic.offsets_s
        for b in batches:
            d = due[b.uid]
            time.sleep(max(0.0, d - clock()))
            run.lateness_s.append(clock() - d)
            with lock:
                submit(b, d)
    time.sleep(max(0.0, run.t1 - clock()))
    with lock:
        state["open"] = False
    collector.join()


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def check_answers(run: Run, rules, answers: Optional[dict] = None
                  ) -> Dict[str, dict]:
    """Every number compared for the MCT wrapper's answers, each with its
    limit: every accepted user query answered, one answer per check, and
    each answer exactly the reference's. ``answers`` (uid -> (decision,
    weight)) puts another matcher's answers in the program's place."""
    tr = run.traffic
    if answers is None:
        answers = {u: (np.asarray(r.decisions), np.asarray(r.weights))
                   for u, r in run.answers.items()}
    accepted = [u for u in run.due if u not in run.rejected]
    out = {"unanswered": {"value": sum(u not in answers for u in accepted),
                          "limit": 0}}
    off, rows, dec, wgt = 0, [], [], []
    for u, (d, w) in answers.items():
        q = tr.queries[run.query_of[u]]
        off += abs(len(d) - q.n_checks)
        k = min(len(d), q.n_checks)
        rows.append(np.arange(q.first_check, q.first_check + k))
        dec.append(np.asarray(d[:k]))
        wgt.append(np.asarray(w[:k]))
    out["answers_missing"] = {"value": off, "limit": 0}
    z = [np.zeros(0, np.int64)]
    rows, dec, wgt = (np.concatenate(a + z) for a in (rows, dec, wgt))
    got = mct.compare_answers(rules, tr.vals[rows.astype(np.int64)],
                              dec, wgt)
    out["mct_checks_compared"] = {"value": got["checks"], "limit": 1,
                                  "at_least": True}
    out["mct_mismatches"] = {"value": got["mismatches"], "limit": 0}
    return out


def control_answers(run: Run, rules) -> dict:
    """The control's answers to the same checks: the first matching rule
    (``mct.first_match_answers``) in place of the most precise."""
    tr = run.traffic
    qs = [(u, tr.queries[run.query_of[u]]) for u in run.answers]
    rows = np.concatenate([np.arange(q.first_check, q.first_check +
                                     q.n_checks) for _, q in qs] +
                          [np.zeros(0, np.int64)]).astype(np.int64)
    dec, wgt = mct.first_match_answers(rules, tr.vals[rows])
    ends = np.cumsum([q.n_checks for _, q in qs])[:-1]
    return {u: dw for (u, _), dw in zip(
        qs, zip(np.split(dec, ends), np.split(wgt, ends)))}


def check_outputs(run: Run, rules, params, *, control: bool = False
                  ) -> Dict[str, dict]:
    """Every number compared for the serving stack, each with its limit.
    ``control`` also reads the float8 control's gaps on the same prompts
    and tokens (``run.gaps``)."""
    tr, cfg, mix = run.traffic, run.cfg, run.mix
    accepted = [rid for rid in run.due if rid not in run.rejected]
    missing = sum(1 for rid in accepted if rid not in run.verdict)
    out = {"unanswered": {"value": missing, "limit": 0}}

    wrong_verdict = 0
    for rid, (_, kind) in run.verdict.items():
        want = "filtered" if tr.queries[run.query_of[rid]].infeasible \
            else "scored"
        wrong_verdict += kind != want
    out["verdict_mismatches"] = {"value": wrong_verdict, "limit": 0}

    if tr.n_checks:
        rows, dec, wgt, rid = run.taps.match_outputs(tr)
        got = mct.compare(rules, tr.vals[rows], dec, wgt, rid)
        out["mct_checks_compared"] = {"value": got["checks"],
                                      "limit": 1, "at_least": True}
        out["mct_mismatches"] = {"value": got["mismatches"], "limit": 0}

    bad_shape = 0
    for rid, c in run.completions.items():
        q = tr.queries[run.query_of[rid]]
        toks = np.asarray(c.tokens)
        bad_shape += (len(toks) != q.new_tokens or c.truncated
                      or bool(((toks < 0) | (toks >= cfg["vocab"])).any()))
    out["token_faults"] = {"value": bad_shape, "limit": 0}

    g = lm_gaps(run, params, control=control)
    run.gaps = g
    out["lm_tokens_compared"] = {"value": int(len(g["program"])),
                                 "limit": 1, "at_least": True}
    out["logit_gap"] = {"value": float(g["program"].max())
                        if len(g["program"]) else math.inf,
                        "limit": cfg.get("logit_gap_limit")}
    return out


def control_checks(run: Run, checks: Dict[str, dict]) -> Dict[str, dict]:
    """The serving stack's checks with the float8 control's widest gap in
    place of the program's."""
    g = run.gaps["control"]
    return dict(checks, logit_gap=dict(
        checks["logit_gap"], value=float(g.max()) if len(g) else math.inf))


def lm_gaps(run: Run, params, *, control: bool = False) -> dict:
    """Gap by which each served token's logit lies below the reference's
    best, over a seeded sample of the answered queries that holds the
    longest of them, of about ``check_tokens`` served tokens; with
    ``control``, the same gap for the token the float8 control puts first.
    Also the worst token: its query, position and both token ids."""
    tr, cfg = run.traffic, run.cfg
    rids = sorted(rid for rid, c in run.completions.items()
                  if len(c.tokens) == tr.queries[run.query_of[rid]].new_tokens)
    out = {"program": np.zeros(0), "control": np.zeros(0), "worst": None}
    if not rids:
        return out
    length = {r: len(tr.queries[run.query_of[r]].prompt) for r in rids}
    longest = max(rids, key=lambda r: length[r])
    per = max(1, tr.queries[run.query_of[longest]].new_tokens)
    n = min(len(rids), max(1, -(-int(run.mix["check_tokens"]) // per)))
    rng = np.random.default_rng([run.seed, 2])
    pick = [longest] + [r for r in rng.permutation(rids)
                        if r != longest][:n - 1]
    groups: Dict[int, list] = {}
    for r in pick:
        groups.setdefault(length[r] + len(run.completions[r].tokens) - 1,
                          []).append(r)
    gaps, ctrl, worst = [], [], (-1.0, None)
    for total, rs in sorted(groups.items()):
        for s in range(0, len(rs), 32):
            part = rs[s:s + 32]
            toks = np.stack([np.concatenate(
                [tr.queries[run.query_of[r]].prompt,
                 np.asarray(run.completions[r].tokens[:-1], np.int32)])
                for r in part])
            served = np.stack([np.asarray(run.completions[r].tokens)
                               for r in part])
            plen = length[part[0]]
            pos = np.broadcast_to(np.arange(plen - 1, total),
                                  served.shape).astype(np.int32)
            ref = lm_ref.logits_at(params, toks, pos, cfg)
            best = ref.max(-1)
            g = best - np.take_along_axis(ref, served[..., None], -1)[..., 0]
            gaps.append(g.reshape(-1))
            i, j = np.unravel_index(int(g.argmax()), g.shape)
            if g[i, j] > worst[0]:
                worst = (float(g[i, j]), {
                    "rid": int(part[i]), "position": int(pos[i, j]),
                    "served": int(served[i, j]),
                    "best": int(ref[i, j].argmax()), "gap": float(g[i, j])})
            if control:
                low = lm_ref.logits_at(params, toks, pos, cfg, quant="fp8")
                c = best - np.take_along_axis(
                    ref, low.argmax(-1)[..., None], -1)[..., 0]
                ctrl.append(c.reshape(-1))
    out["program"] = np.concatenate(gaps)
    out["control"] = np.concatenate(ctrl) if ctrl else np.zeros(0)
    out["worst"] = worst[1]
    return out


def gap_stats(g: np.ndarray) -> dict:
    if not len(g):
        return {}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)),
            "nonzero_share": float((g > 0).mean())}


def is_correct(checks: Dict[str, dict]) -> bool:
    ok = True
    for c in checks.values():
        if c["limit"] is None:          # no limit set yet: not proven
            ok = False
        elif c.get("at_least"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, bench_dir: Path = BENCH_DIR,
             spec: Optional[dict] = None, control: bool = False,
             mix_override: Optional[dict] = None,
             keep_trace_dir: Optional[str] = None,
             runs_out: Optional[list] = None) -> dict:
    """One run of ``cell_name``; the keyword-only extras serve
    ``calibrate.py`` (a cell held out of ``BENCHMARK.json``, the control,
    a changed mix, the raw trace, the Run itself) and are never used by
    ``run.py``."""
    import jax

    seed = int(seed) % 2**63               # SeedSequence takes no negatives
    spec = spec or load_spec(root)
    cell = cell_of(spec, cell_name)
    cfg = load_config(bench_dir, cell["config"])
    mix = dict(load_mix(bench_dir, cell["traffic"]), **(mix_override or {}))
    dev = jax.devices()[0]
    run = Run(cell=cell_name, seed=seed, cfg=cfg, mix=mix,
              seconds=float(seconds), bench_dir=bench_dir,
              device_kind=dev.device_kind)
    serves_lm = "model" in cfg
    counter = _CompileCounter()

    # -- set-up: data, weights, the entry ---------------------------------
    n_q = traffic.n_queries(mix, seconds)
    carries_checks = mix["user_query"]["direct_share"] < 1.0
    rules = mct.generate_rules(cfg["rules"]["n_rules"],
                               np.random.default_rng([seed, 0])) \
        if carries_checks else None
    run.traffic = tr = traffic.build(mix, seconds, seed, cfg.get("vocab", 2),
                                     rules)
    engine = program.rule_engine(rules) if rules is not None else None
    if engine is not None:
        run.table_shape = tuple(engine.dt.mins_t.shape)
    params = None
    if serves_lm:
        params = lm_ref.init_params(cfg, jax_seed(seed))
        s = cfg["serve"]
        buckets = [1 << i for i in range(int(s["target_batch"]).bit_length())]
        server, taps = program.build_server(cfg, params, engine, buckets)
        sess = server.session()
        reqs = program.requests(tr)
        jax.block_until_ready(params)
    else:
        server, taps = program.build_wrapper(cfg, engine)
        batches = program.batches(tr)
    run.taps = taps
    run.setup_s = time.perf_counter() - t_start

    # -- the window -----------------------------------------------------------
    cache_was = jax.config.jax_enable_compilation_cache
    _persistent_cache(False)
    try:
        counter.on = taps.on = True
        tracer = _Tracer(time.perf_counter(), seconds, keep_trace_dir) \
            if trace else None
        if serves_lm:
            _drive(run, sess, reqs)
            comps = sess.result()         # the window's queries drain
        else:
            _drive_wrapper(run, server, batches)
            comps = []
        run.drain_end = time.perf_counter()
        counter.on = taps.on = False
    finally:
        counter.close()
        _persistent_cache(cache_was)
    run.compiles = counter.n
    if serves_lm:
        run.report = sess.report()
    if tracer is not None:
        run.trace = tracer.result()
        run.modules = _module_names(taps, engine, params, cfg)
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    for c in comps:                       # completions the hooks missed
        run.completions.setdefault(c.rid, c)

    # -- free the program's state, then compare -------------------------------
    if serves_lm:
        server.close()
        del sess, comps
    else:
        server.stop()
    del server
    gc.collect()
    if serves_lm:
        checks = check_outputs(run, rules, params, control=control)
        ctrl = control_checks(run, checks) if control else None
    else:
        checks = check_answers(run, rules)
        ctrl = check_answers(run, rules, control_answers(run, rules)) \
            if control else None
    correct = is_correct(checks)

    # -- metrics ---------------------------------------------------------------
    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        v = load_reader(bench_dir, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(run.due),
              "failed": len(run.rejected) + checks["unanswered"]["value"],
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = trace_reduce.window_s(run.trace)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(run.trace, 10),
            "idle_gaps": trace_reduce.idle_gaps(run.trace, 10)}
    result["checks"] = checks
    if ctrl is not None:
        result["control"] = {"correct": is_correct(ctrl), "checks": ctrl}
    if runs_out is not None:
        run.params = params
        runs_out.append(run)
    late = np.asarray(run.lateness_s or [0.0])
    result["_log"] = {
        "setup_s": run.setup_s, "queries": n_q, "checks": tr.n_checks,
        "answered": len(run.verdict), "rejected": len(run.rejected),
        "window_compiles": run.compiles,
        "generator_late_ms_p50": float(np.percentile(late, 50) * 1e3),
        "generator_late_ms_max": float(late.max() * 1e3)}
    if serves_lm:
        result["_log"].update(
            gap=gap_stats(run.gaps["program"]),
            control_gap=gap_stats(run.gaps["control"]),
            worst_token=run.gaps["worst"])
    return result


def _module_names(taps, engine, params, cfg: dict) -> Dict[str, str]:
    """The program names of the decode step and the match, as the
    profiler shows them, read from their lowering (shapes only)."""
    import re

    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    names = {}
    if taps.decode_jit is not None:
        shapes = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                        params)
        low = taps.decode_jit.lower(
            shapes, taps.model.cache_struct(1, cfg["serve"]["max_seq"]),
            sds((1, 1), jnp.int32), sds((), jnp.int32))
        names["decode"] = re.search(r"module @(\S+)", low.as_text()).group(1)
    if engine is not None:
        from repro.kernels import ops
        low = ops.match_rules.lower(
            sds((256, engine.table.n_cols), jnp.int32), engine.dt,
            tile_b=engine.tile_b, tile_r=engine.tile_r,
            backend=engine.backend, n_engines=engine.n_engines)
        names["match"] = re.search(r"module @(\S+)", low.as_text()).group(1)
    return names
