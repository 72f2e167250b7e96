"""The benchmark's only contact with the program under test.

It turns the benchmark's seeded data into the program's inputs (a
``RuleSet`` for ``compile_rules``, ``Request`` objects or ``Batch``
objects with raw check dicts) and builds one of two entries: the serving
stack through ``ServeConfig``/``build`` with the benchmark's weights, or
the MCT rule engine behind the program's multi-threaded host executor
(``core/wrapper.MCTWrapper``). It wraps calls on the built objects with
``jax.profiler.TraceAnnotation`` spans (as ``chip_smoke.py`` wraps them):
``prepare_batch``, ``execute_prepared``, the wrapper's execution of a
batch, ``ErbiumEngine.match`` and the jitted decode step. The wrappers
also keep what the serving stack's timed path produced, for the
comparison with the reference once the window has closed.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List

import numpy as np

from bench import mct

# the config keys that must agree with the program's own ModelConfig
_SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "rope_theta", "norm_eps", "parallel_ssm")


def model_config(cfg: dict):
    """The program's ModelConfig for ``cfg``: its published config with the
    cuts the file lists, checked key by key against the file."""
    from repro.configs.base import get_config
    mc = get_config(cfg["model"])
    mc = dataclasses.replace(mc, n_layers=cfg["n_layers"])
    for k in _SIZE_KEYS:
        if getattr(mc, k) != cfg[k]:
            raise ValueError(f"{cfg['name']}: {k} is {cfg[k]} in the file "
                             f"and {getattr(mc, k)} in the program")
    if tuple(mc.attn_pattern[i % len(mc.attn_pattern)]
             for i in range(mc.n_layers)) != tuple(
            cfg["attn_pattern"][i % len(cfg["attn_pattern"])]
            for i in range(cfg["n_layers"])):
        raise ValueError(f"{cfg['name']}: attention windows differ")
    if cfg.get("parallel_ssm"):
        for k, v in cfg["ssm"].items():
            if getattr(mc.ssm, k) != v:
                raise ValueError(f"{cfg['name']}: ssm.{k} differs")
    return mc


def rule_engine(rules: mct.Rules):
    """Compile the rules with the program's offline compiler and load them
    into its matcher."""
    from repro.core.compiler import compile_rules
    from repro.core.engine import ErbiumEngine
    from repro.core.rules import WILDCARD, Rule, RuleSet, schema_v2
    schema = schema_v2()
    names = [c.name for c in schema]
    if names != [s[0] for s in mct.SCHEMA_V2] or any(
            c.weight != s[3] for c, s in zip(schema, mct.SCHEMA_V2)):
        raise ValueError("the program's MCT v2 schema differs from the "
                         "benchmark's copy")
    cols = []
    for k, (name, kind, _, _, _) in enumerate(mct.SCHEMA_V2):
        lo, hi, b = (rules.lo[:, k].tolist(), rules.hi[:, k].tolist(),
                     rules.bound[:, k].tolist())
        if kind == "cat":
            cols.append([v if ok else WILDCARD for v, ok in zip(lo, b)])
        else:
            cols.append([(a, z) if ok else WILDCARD
                         for a, z, ok in zip(lo, hi, b)])
    dec = rules.decision.tolist()
    ruleset = RuleSet(schema=schema, version=2, rules=[
        Rule(values=dict(zip(names, vals)), decision=dec[i], rule_id=i)
        for i, vals in enumerate(zip(*cols))])
    if ruleset.default_decision != mct.DEFAULT_DECISION:
        raise ValueError("the program's default MCT decision differs")
    return ErbiumEngine(compile_rules(ruleset))


def _check_dicts(traffic) -> List[list]:
    """Each user query's checks as the program's raw query dicts."""
    fields = sorted(traffic.checks)
    cols = [traffic.checks[f].tolist() for f in fields]
    rows = list(zip(*cols)) if cols else []
    return [[dict(zip(fields, r)) for r in
             rows[q.first_check:q.first_check + q.n_checks]]
            for q in traffic.queries]


def requests(traffic) -> list:
    """One program ``Request`` per user query, its checks as raw dicts."""
    from repro.serve.engine import Request
    return [Request(rid=q.uid, tokens=q.prompt, max_new_tokens=q.new_tokens,
                    mct_queries=checks,
                    connect_minutes=q.connect_minutes.tolist())
            for q, checks in zip(traffic.queries, _check_dicts(traffic))]


def batches(traffic) -> list:
    """One program ``Batch`` per user query that holds a connection: all
    its checks in one call, as the aggregator's ``greedy_all`` forms it. A
    user query with no connection asks the MCT engine nothing."""
    from repro.core.aggregator import Batch
    return [Batch(uid=q.uid, queries=checks,
                  ts_index=[(q.uid, j) for j in range(len(checks))])
            for q, checks in zip(traffic.queries, _check_dicts(traffic))
            if checks]


class Taps:
    """The four wrapped calls: spans, host times and what they produced."""

    def __init__(self):
        self.on = False               # record only inside the window
        self._lock = threading.Lock()
        self._local = threading.local()
        self.prepare: List[tuple] = []   # (seconds, checks encoded)
        self.matches: List[tuple] = []   # (rids, (decision, weight, rule))
        self.decode_jit = None           # the program's jitted step
        self.model = None

    def install_wrapper(self, wrapper, engine) -> None:
        """Spans around the wrapper's execution of a batch and the match;
        the wrapper's own results are what the window produced."""
        import jax
        ann = jax.profiler.TraceAnnotation
        execute = wrapper._execute

        def execute_batch(t_in, batch, eng_idx):
            with ann("bench.execute", checks=len(batch.queries)):
                return execute(t_in, batch, eng_idx)

        wrapper._execute = execute_batch
        self._span_match(engine, keep=False)

    def install(self, server, engine) -> None:
        import jax
        ann = jax.profiler.TraceAnnotation
        prepare, execute, decode = (server.prepare_batch,
                                    server.execute_prepared, server._decode)
        self.decode_jit, self.model = decode, server.model

        def prepare_batch(requests):
            t0 = time.perf_counter()
            with ann("bench.prepare_batch"):
                pb = prepare(requests)
            if self.on:
                n = 0 if pb.mct_encoded is None else len(pb.mct_encoded)
                with self._lock:
                    self.prepare.append((time.perf_counter() - t0, n))
            return pb

        def execute_prepared(pb, *, device=None):
            self._local.rids = [r.rid for r in pb.requests]
            with ann("bench.execute_prepared"):
                return execute(pb, device=device)

        def decode_step(*args):
            with ann("bench.decode_step"):
                return decode(*args)

        server.prepare_batch = prepare_batch
        server.execute_prepared = execute_prepared
        server._decode = decode_step
        if engine is not None:
            self._span_match(engine, keep=True)

    def _span_match(self, engine, *, keep: bool) -> None:
        import jax
        match = engine.match

        def match_call(encoded, device=None):
            with jax.profiler.TraceAnnotation("bench.match",
                                              checks=len(encoded)):
                out = match(encoded, device=device)
            if keep and self.on:
                with self._lock:
                    self.matches.append((self._local.rids, out))
            return out

        engine.match = match_call

    def match_outputs(self, traffic):
        """(check rows into the traffic's arrays, decision, weight, rule)
        of every check the window matched, in the order matched."""
        first = {q.uid: (q.first_check, q.n_checks) for q in traffic.queries}
        rows, dec, wgt, rid = [], [], [], []
        for rids, (d, w, r) in self.matches:
            rows.append(np.concatenate(
                [np.arange(first[u][0], first[u][0] + first[u][1])
                 for u in rids] or [np.zeros(0, np.int64)]))
            dec.append(np.asarray(d))
            wgt.append(np.asarray(w))
            rid.append(np.asarray(r))
        if not rows:
            z = np.zeros(0, np.int64)
            return z, z, z, z
        return tuple(np.concatenate(a) for a in (rows, dec, wgt, rid))


def build_server(cfg: dict, params, engine, buckets):
    """The serving stack as ``build()`` makes it, on the benchmark's
    weights, warmed through the program's own ``Server.warmup``."""
    from repro.serve import ServeConfig, build
    from repro.serve.engine import LMServer
    mc = model_config(cfg)
    s = cfg["serve"]
    taps = Taps()

    def factory(_):
        srv = LMServer(mc, params=params, max_seq=s["max_seq"],
                       rule_filter=engine)
        taps.install(srv, engine)
        return srv

    server = build(ServeConfig(
        server_factory=factory, target_batch=s["target_batch"],
        deadline=s["deadline_s"], max_queue=s["max_queue"],
        pipeline_depth=s["pipeline_depth"], warmup=tuple(buckets)))
    return server, taps


def build_wrapper(cfg: dict, engine):
    """The MCT engine behind the program's host executor, its worker
    threads started; nothing is warmed, as the program warms nothing."""
    from repro.core.wrapper import MCTWrapper
    wrapper = MCTWrapper([engine], n_workers=int(cfg["wrapper"]["n_workers"]))
    taps = Taps()
    taps.install_wrapper(wrapper, engine)
    wrapper.start()
    return wrapper, taps
