"""Serving engine: batched prefill + decode with the paper's batch-formation
policy driving request aggregation.

The paper's lesson (§5): the accelerator is only competitive when the
integration layer forms large enough batches — so the server's front end IS
the DeadlineAggregator (target batch + SLA deadline), and the MCT rule
engine plugs in as a request-filtering stage ahead of the LM (the paper's
Fig 14 co-location of MCT + Route Scoring on one accelerator).

The batched path is split into a host-side **prepare** stage (token-matrix
assembly + MCT query encoding, pure numpy) and a device-side **execute**
stage (rule matching + decode loop). ``repro.serve.group.EngineGroup`` and
``repro.serve.scheduler`` exploit the split to overlap host encode of batch
N+1 with device execution of batch N — the imbalance the paper's §5–6
identify as the deployment's make-or-break.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.aggregator import DeadlineAggregator
from repro.models.registry import build_model


@dataclass
class Request:
    rid: int
    tokens: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16
    arrival: float = 0.0
    # MCT filtering stage inputs: connection queries + actual connect times
    mct_queries: List[Dict[str, int]] = field(default_factory=list)
    connect_minutes: List[int] = field(default_factory=list)


@dataclass
class Completion:
    rid: int
    tokens: np.ndarray            # generated ids
    prefill_ms: float
    decode_ms: float
    batch_size: int
    truncated: bool = False       # hit the max_seq context limit before
                                  # max_new_tokens were produced


@dataclass
class PreparedBatch:
    """Host-side half of a batch: everything the device stage needs,
    assembled without touching the accelerator."""
    requests: List[Request]
    toks: np.ndarray                      # (B, max_plen) int32
    plens: List[int]
    max_new: int
    mct_encoded: Optional[np.ndarray]     # (Q, C) int32 or None
    mct_owner: List[int] = field(default_factory=list)  # query -> request idx


def form_batch_groups(requests: Sequence[Request], *, target_batch: int = 8,
                      deadline: float = 0.05) -> List[List[Request]]:
    """Replay an arrival-ordered request stream through the paper's
    deadline policy; logical time, so batch composition is deterministic
    for a given stream. Engine-independent: any server implementing the
    prepare/execute protocol (LMServer, SimServer) can run the groups."""
    agg = DeadlineAggregator(target_batch=target_batch, deadline=deadline)
    batches = []
    for r in sorted(requests, key=lambda x: x.arrival):
        batches.extend(agg.offer(r.rid, [r], now=r.arrival))
    batches.extend(agg.flush())
    return [[q for q in b.queries] for b in batches]


class LMServer:
    """Batched prefill + decode-loop serving for any registry architecture."""

    def __init__(self, cfg: ModelConfig, params=None, *, ctx=None,
                 max_seq: int = 256, seed: int = 0,
                 rule_filter=None, pad_batches: bool = True):
        self.cfg = cfg
        self.model = build_model(cfg, ctx)
        self.params = params if params is not None \
            else self.model.init(jax.random.PRNGKey(seed))
        self.max_seq = max_seq
        self.rule_filter = rule_filter      # optional ErbiumEngine stage
        # batch-size bucketing: pad each batch to the next power of two so
        # the jitted decode step compiles O(log B) variants instead of one
        # per distinct batch size — without it, a deadline-formed stream of
        # ragged batches is a compile storm. Rows are independent (masked
        # attention), so padding never changes per-request results.
        self.pad_batches = pad_batches
        self._decode = jax.jit(
            lambda p, c, t, pos: self.model.decode_step(p, c, t, pos),
            donate_argnums=(1,))
        self._dev_params: Dict[object, object] = {}

    # -- host-side prepare stage ----------------------------------------------
    def prepare_batch(self, requests: Sequence[Request]) -> PreparedBatch:
        """Assemble the token matrix and encode MCT queries — pure host
        (numpy) work, safe to run while the device executes another batch."""
        rs = list(requests)
        plens = [len(r.tokens) for r in rs]
        max_new = max((r.max_new_tokens for r in rs), default=0)
        toks = np.zeros((len(rs), max(plens, default=0)), np.int32)
        for i, r in enumerate(rs):
            toks[i, :plens[i]] = r.tokens
        mct_encoded, owner = None, []
        if self.rule_filter is not None:
            flat = []
            for i, r in enumerate(rs):
                for q in r.mct_queries:
                    flat.append(q)
                    owner.append(i)
            if flat:
                mct_encoded = self.rule_filter.encode_queries_host(flat)
        return PreparedBatch(requests=rs, toks=toks, plens=plens,
                             max_new=max_new, mct_encoded=mct_encoded,
                             mct_owner=owner)

    # -- device-side execute stage --------------------------------------------
    def execute_prepared(self, pb: PreparedBatch, *,
                         device=None) -> List[Completion]:
        """Run the device half: MCT rule matching (drops infeasible
        requests), then the batched prefill + decode loop. ``device`` pins
        execution to a specific jax device (the scheduler round-robins
        batches across devices when given several)."""
        rs = pb.requests
        if not rs:
            return []
        toks, plens, max_new = pb.toks, pb.plens, pb.max_new
        if self.rule_filter is not None and pb.mct_encoded is not None:
            keep = self._mct_feasible(rs, pb.mct_encoded, pb.mct_owner,
                                      device=device)
            if not all(keep):
                # slice the already-prepared rows — no host re-encode on
                # the device thread's critical path
                idx = [i for i, ok in enumerate(keep) if ok]
                if not idx:
                    return []
                rs = [rs[i] for i in idx]
                toks = toks[idx]
                plens = [plens[i] for i in idx]
                max_new = max(r.max_new_tokens for r in rs)
        return self._run_decode(rs, toks, plens, max_new, device=device)

    def generate_batch(self, requests: Sequence[Request]) -> List[Completion]:
        """prepare + execute in one synchronous call (the baseline path).
        Applies the MCT filter stage when the server has one."""
        if not requests:
            return []
        return self.execute_prepared(self.prepare_batch(requests))

    def warmup(self, batch_sizes: Sequence[int] = (1, 8), *,
               prompt_len: int = 4, max_new_tokens: int = 2) -> None:
        """Pre-compile the decode step for the bucketed batch sizes so the
        first live batches don't pay JIT latency (benchmarks call this
        before timing)."""
        for b in batch_sizes:
            reqs = [Request(rid=-1 - i,
                            tokens=np.ones(prompt_len, np.int32),
                            max_new_tokens=max_new_tokens, mct_queries=[],
                            connect_minutes=[])
                    for i in range(b)]
            self._run_decode(reqs, np.ones((b, prompt_len), np.int32),
                             [prompt_len] * b, max_new_tokens)

    def _params_on(self, device):
        if device is None:
            return self.params
        if device not in self._dev_params:
            self._dev_params[device] = jax.device_put(self.params, device)
        return self._dev_params[device]

    def _run_decode(self, rs: List[Request], toks: np.ndarray,
                    plens: List[int], max_new: int,
                    device=None) -> List[Completion]:
        t0 = time.perf_counter()
        B = len(rs)
        total = self.max_seq
        max_p = max(plens)
        if max_p >= total:
            # hard error, not an assert: the scheduler's worker-death
            # propagation relies on this raising even under python -O,
            # and proceeding would silently corrupt the KV cache
            raise ValueError(
                f"max_seq={total} too small for the prompt alone "
                f"(longest prompt: {max_p})")

        Bp = B
        if self.pad_batches and B > 1:
            Bp = 1 << (B - 1).bit_length()      # next power of two
        if Bp != B:
            toks = np.concatenate(
                [toks, np.zeros((Bp - B, toks.shape[1]), np.int32)])

        params = self._params_on(device)
        # the cache, step tokens and argmax live on the replica's device
        with jax.default_device(device):
            cache = self.model.init_cache(Bp, total)
            # prefill via the decode path, token by token up to each prompt
            # len (keeps one compiled step; a fused prefill kernel is the
            # fast path for attention archs, exercised in tests via
            # model.prefill)
            generated = [[] for _ in range(B)]
            last_logits = None
            for pos in range(max_p):
                step_tok = jnp.asarray(toks[:, pos:pos + 1])
                last_logits, cache = self._decode(params, cache, step_tok,
                                                  jnp.int32(pos))
            t1 = time.perf_counter()

            cur = np.asarray(jnp.argmax(last_logits[:, -1], axis=-1),
                             np.int32)
            for s in range(max_new):
                for i in range(B):
                    if s < rs[i].max_new_tokens:
                        generated[i].append(int(cur[i]))
                pos = max_p + s
                if pos >= total - 1 or s == max_new - 1:
                    break
                logits, cache = self._decode(params, cache,
                                             jnp.asarray(cur[:, None]),
                                             jnp.int32(pos))
                cur = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                 np.int32)
            jax.block_until_ready(cur)
            t2 = time.perf_counter()

        return [Completion(rid=r.rid, tokens=np.asarray(g, np.int32),
                           prefill_ms=(t1 - t0) * 1e3,
                           decode_ms=(t2 - t1) * 1e3, batch_size=B,
                           truncated=len(g) < r.max_new_tokens)
                for r, g in zip(rs, generated)]

    # -- continuous batching front end ----------------------------------------
    def form_batches(self, requests: Sequence[Request], *,
                     target_batch: int = 8, deadline: float = 0.05
                     ) -> List[List[Request]]:
        """Replay an arrival-ordered request stream through the paper's
        deadline policy (see module-level :func:`form_batch_groups`)."""
        return form_batch_groups(requests, target_batch=target_batch,
                                 deadline=deadline)

    def _mct_feasible(self, rs: List[Request], encoded: np.ndarray,
                      owner: List[int], device=None) -> List[bool]:
        """MCT filtering stage: all connection queries of the batch were
        encoded host-side into ONE kernel input (the paper's aggregation
        lesson); match on ``device``, then drop requests with an
        infeasible connection (connect time < MCT)."""
        dec, _, _ = self.rule_filter.match(encoded, device=device)
        dec = np.asarray(dec)
        feasible = [True] * len(rs)
        pos = {i: 0 for i in range(len(rs))}
        for j, i in enumerate(owner):
            mct = int(dec[j])
            if mct < 0:
                mct = self.rule_filter.table.default_decision
            have = rs[i].connect_minutes[pos[i]] \
                if pos[i] < len(rs[i].connect_minutes) else 10 ** 6
            pos[i] += 1
            if have < mct:
                feasible[i] = False
        return feasible
