"""One general generator for every traffic mix in ``traffic/<mix>.json``.

A mix is data: the loop (``open`` with a Poisson rate, or ``closed`` with
a number of clients), the Domain Explorer shape of a user query (travel
solutions per query, the direct share, checks per connecting solution,
the cap), the share of queries that hold an infeasible connection, and
prompt and output lengths for the scoring model.

Every seed gets the same work in another order: the sizes of the user
queries (checks, infeasibility, prompt length) and the gaps between
arrivals are drawn once from the mix's own ``sizes_seed``; the run's seed
permutes them (a closed loop's pool within blocks of one query per
client) and draws the contents (which rules a check comes from, the
prompt tokens). So runs of different seeds differ in what they ask, not
in how much.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench import mct


@dataclass
class UserQuery:
    uid: int
    prompt: np.ndarray            # (plen,) int32 token ids
    new_tokens: int
    first_check: int              # slice of the traffic's check arrays
    n_checks: int
    connect_minutes: np.ndarray   # (n_checks,)
    infeasible: bool              # holds a connection shorter than any MCT


@dataclass
class Traffic:
    mix: dict
    queries: List[UserQuery]
    offsets_s: np.ndarray         # open loop: due time of query i
    checks: Dict[str, np.ndarray]  # raw check fields, all queries together
    vals: np.ndarray              # (n_checks, 26) effective values

    @property
    def n_checks(self) -> int:
        return len(self.vals)


def _sizes(mix: dict, n: int) -> Dict[str, np.ndarray]:
    """The seed-independent multiset of query sizes."""
    rng = np.random.default_rng(mix["sizes_seed"])
    u = mix["user_query"]
    n_ts = np.clip(rng.lognormal(np.log(u["ts_mean"]) - 0.5 * u["ts_sigma"]
                                 ** 2, u["ts_sigma"], n),
                   u["ts_min"], u["ts_max"]).astype(np.int64)
    n_conn = n_ts - rng.binomial(n_ts, u["direct_share"])
    per = np.clip(rng.geometric(1.0 / u["checks_per_connecting_ts"],
                                int(n_conn.sum())), 1,
                  u["checks_per_connecting_ts_max"])
    ends = np.cumsum(n_conn)
    tot = np.add.reduceat(np.concatenate([per, [0]]),
                          np.concatenate([[0], ends[:-1]])) \
        if len(per) else np.zeros(n, np.int64)
    tot = np.where(n_conn > 0, tot, 0)[:n]
    checks = np.minimum(tot, u["max_checks"]).astype(np.int64)
    infeasible = (rng.random(n) < mix["infeasible_share"]) & (checks > 0)
    lo, hi = mix["prompt_tokens"]
    plen = rng.integers(lo, hi + 1, n)
    return {"checks": checks, "infeasible": infeasible, "plen": plen}


def n_queries(mix: dict, seconds: float) -> int:
    """How many user queries a run of ``seconds`` offers: the open loop's
    rate times the window, or the closed loop's pool."""
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return int(mix["pool"])


def build(mix: dict, seconds: float, seed: int, vocab: int,
          rules) -> Traffic:
    n = n_queries(mix, seconds)
    size = _sizes(mix, n)
    rng = np.random.default_rng([seed, 1])
    if mix["loop"] == "closed":
        # clients take queries in blocks of one per client, so the first
        # queries of any seed, which a window serves, are the same sizes
        c = int(mix["clients"])
        order = np.concatenate([k + rng.permutation(min(c, n - k))
                                for k in range(0, n, c)])
    else:
        order = rng.permutation(n)
    checks_n = size["checks"][order]
    infeasible = size["infeasible"][order]
    plen = size["plen"][order]
    total = int(checks_n.sum())
    if total and rules is None:
        raise ValueError("the mix carries MCT checks but no rules were made")
    checks = mct.generate_checks(rules, total, rng,
                                 match_bias=mix["check_match_bias"],
                                 code_share_p=mix["code_share_share"]) \
        if total else {}
    vals = mct.effective_values(checks) if total else \
        np.zeros((0, len(mct.SCHEMA_V2)), np.int64)
    short, long_ = mix["connect_minutes"]["short"], \
        mix["connect_minutes"]["long"]
    if not short < min(mct.DECISIONS) or not long_ > mct.DEFAULT_DECISION:
        raise ValueError("connection times must lie outside every MCT "
                         "decision, so that a verdict needs no tie-break")
    queries, first = [], 0
    for i in range(n):
        k = int(checks_n[i])
        conn = np.full(k, long_, np.int64)
        if infeasible[i]:
            conn[rng.integers(0, k)] = short
        queries.append(UserQuery(
            uid=i, prompt=rng.integers(0, vocab, int(plen[i])).astype(
                np.int32),
            new_tokens=int(mix["new_tokens"]), first_check=first,
            n_checks=k, connect_minutes=conn, infeasible=bool(infeasible[i])))
        first += k
    offsets = np.zeros(n)
    if mix["loop"] == "open":
        gaps = np.random.default_rng(mix["sizes_seed"] + 1).exponential(
            1.0, n)
        gaps = gaps / gaps.sum() * seconds       # n arrivals in the window
        offsets = np.cumsum(rng.permutation(gaps))
    return Traffic(mix=mix, queries=queries, offsets_s=offsets,
                   checks=checks, vals=vals)
