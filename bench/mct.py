"""MCT rules and checks: a seeded vectorised generator and a plain matcher.

The generator keeps the statistics of the program's ``generate_rules`` and
``generate_queries`` (Zipf-skewed categories, 55% wildcards, rare narrow
flight-number ranges, 70% of checks derived from a rule, 15% code-share)
but draws whole columns at once, so 160,000 rules take well under a
second. The schema below is this file's own copy of MCT v2 (26 criteria,
two of them cross-matched through the code-share flag); the reference
matcher works on it and on the raw values alone, so it shares nothing
with the program under test.

Semantics the reference decides: a rule matches a check when every bound
criterion holds (a category equals the check's value, a range contains
it); among matching rules the highest precision weight wins; no match
means the default decision. Ties in weight may go to any of the tied
rules, so the comparison accepts any rule of the best weight.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

WILDCARD = -1
DEFAULT_DECISION = 999
DECISIONS = (20, 25, 30, 35, 40, 45, 60, 75, 90, 120)

# (name, kind, cardinality or (lo, hi) domain, intrinsic weight,
#  (field when code-share, field otherwise, code-share flag) or None)
SCHEMA_V2: Tuple[tuple, ...] = (
    ("airport", "cat", 500, 64, None),
    ("arr_terminal", "cat", 12, 16, None),
    ("dep_terminal", "cat", 12, 16, None),
    ("arr_region", "cat", 8, 8, None),
    ("dep_region", "cat", 8, 8, None),
    ("arr_country", "cat", 240, 24, None),
    ("dep_country", "cat", 240, 24, None),
    ("arr_mkt_carrier", "cat", 900, 32,
     ("arr_mkt_carrier", "arr_mkt_carrier", "arr_cs")),
    ("arr_op_carrier", "cat", 900, 28,
     ("arr_op_carrier", "arr_mkt_carrier", "arr_cs")),
    ("dep_mkt_carrier", "cat", 900, 32,
     ("dep_mkt_carrier", "dep_mkt_carrier", "dep_cs")),
    ("dep_op_carrier", "cat", 900, 28,
     ("dep_op_carrier", "dep_mkt_carrier", "dep_cs")),
    ("arr_flight_kind", "cat", 4, 4, None),
    ("dep_flight_kind", "cat", 4, 4, None),
    ("arr_aircraft", "cat", 50, 8, None),
    ("dep_aircraft", "cat", 50, 8, None),
    ("prev_airport", "cat", 500, 12, None),
    ("next_airport", "cat", 500, 12, None),
    ("arr_state", "cat", 60, 6, None),
    ("dep_state", "cat", 60, 6, None),
    ("weekday", "cat", 8, 4, None),
    ("season", "cat", 4, 4, None),
    ("arr_flightno", "range", (0, 9_999), 48, None),
    ("dep_flightno", "range", (0, 9_999), 48, None),
    ("date", "range", (0, 730), 16, None),
    ("arr_cs_flightno", "range", (0, 9_999), 40,
     ("arr_cs_flightno", "arr_flightno", "arr_cs")),
    ("dep_cs_flightno", "range", (0, 9_999), 40,
     ("dep_cs_flightno", "dep_flightno", "dep_cs")),
)
CS_FLAGS = ("arr_cs", "dep_cs")


def _zipf(rng, n: int, size, a: float = 1.3) -> np.ndarray:
    return np.minimum(rng.zipf(a, size=size) - 1, n - 1).astype(np.int64)


@dataclass
class Rules:
    """Raw rules, column by column: ``lo``/``hi`` (R, K) bounds per
    criterion (a category has lo == hi), ``bound`` (R, K) whether the
    criterion is bound, ``decision`` (R,), ``weight`` (R,)."""
    lo: np.ndarray
    hi: np.ndarray
    bound: np.ndarray
    decision: np.ndarray
    weight: np.ndarray

    @property
    def n(self) -> int:
        return len(self.decision)


def generate_rules(n: int, rng: np.random.Generator,
                   wildcard_p: float = 0.55,
                   narrow_p: float = 0.1) -> Rules:
    K = len(SCHEMA_V2)
    lo = np.zeros((n, K), np.int64)
    hi = np.zeros((n, K), np.int64)
    bound = rng.random((n, K)) >= wildcard_p
    bound[:, 0] = True                       # every rule names its airport
    for k, (name, kind, dom, _, _) in enumerate(SCHEMA_V2):
        if kind == "cat":
            v = _zipf(rng, dom, n)
            lo[:, k] = hi[:, k] = v
        else:
            d0, d1 = dom
            a = rng.integers(d0, d1, n)
            width = rng.integers(1, np.maximum((d1 - a) // 4, 2))
            width = np.where(rng.random(n) < narrow_p,
                             np.maximum(width // 8, 1), width)
            lo[:, k], hi[:, k] = a, np.minimum(a + width, d1)
    decision = np.asarray(DECISIONS)[rng.integers(0, len(DECISIONS), n)]
    return Rules(lo=lo, hi=hi, bound=bound, decision=decision,
                 weight=rule_weights(lo, hi, bound))


def rule_weights(lo, hi, bound) -> np.ndarray:
    """MCT v2 precision weight: the intrinsic weights of the bound
    criteria, less a penalty of ceil(log2(size + 1)), at most half the
    weight, for each bound range of ``size`` values."""
    w = np.zeros(len(lo), np.int64)
    for k, (_, kind, _, cw, _) in enumerate(SCHEMA_V2):
        wk = np.full(len(lo), cw, np.int64)
        if kind == "range":
            size = np.maximum(hi[:, k] - lo[:, k], 0) + 1
            pen = np.ceil(np.log2(size + 1)).astype(np.int64)
            wk -= np.minimum(pen, cw // 2)
        w += np.where(bound[:, k], wk, 0)
    return w


def generate_checks(rules: Rules, n: int, rng: np.random.Generator,
                    match_bias: float = 0.7,
                    code_share_p: float = 0.15) -> Dict[str, np.ndarray]:
    """``n`` raw checks, one array per query field (the 26 criteria plus
    the two code-share flags). A share ``match_bias`` copies a random
    rule's bound values, so matches exist."""
    base = rng.integers(0, rules.n, n)
    derived = rng.random(n) < match_bias
    out: Dict[str, np.ndarray] = {}
    for k, (name, kind, dom, _, _) in enumerate(SCHEMA_V2):
        use = derived & rules.bound[base, k]
        if kind == "cat":
            free = _zipf(rng, dom, n)
            out[name] = np.where(use, rules.lo[base, k], free)
        else:
            free = rng.integers(dom[0], dom[1], n)
            inside = rules.lo[base, k] + (
                rng.random(n) * (rules.hi[base, k] - rules.lo[base, k] + 1)
            ).astype(np.int64)
            out[name] = np.where(use, inside, free)
    names = [s[0] for s in SCHEMA_V2]
    for side in ("arr", "dep"):
        op = names.index(f"{side}_op_carrier")
        csf = names.index(f"{side}_cs_flightno")
        bound = derived & (rules.bound[base, op] | rules.bound[base, csf])
        cs = bound | (rng.random(n) < code_share_p)
        out[f"{side}_cs"] = cs.astype(np.int64)
        out[f"{side}_op_carrier"] = np.where(
            cs, out[f"{side}_op_carrier"], out[f"{side}_mkt_carrier"])
    return out


def effective_values(checks: Dict[str, np.ndarray]) -> np.ndarray:
    """(N, 26) value each criterion is judged on: a cross-matched
    criterion reads its code-share field when the flag is set."""
    cols = []
    for name, _, _, _, cross in SCHEMA_V2:
        if cross is None:
            cols.append(checks[name])
        else:
            when_cs, otherwise, flag = cross
            cols.append(np.where(checks[flag].astype(bool),
                                 checks[when_cs], checks[otherwise]))
    return np.stack(cols, axis=1).astype(np.int64)


def rule_matches(rules: Rules, rows: np.ndarray, vals: np.ndarray
                 ) -> np.ndarray:
    """Whether rule ``rows[i]`` matches check ``vals[i]`` (host, per pair)."""
    lo, hi, b = rules.lo[rows], rules.hi[rows], rules.bound[rows]
    return np.all(~b | ((vals >= lo) & (vals <= hi)), axis=1)


def dense_pass(rules: Rules, vals: np.ndarray, block: int = 256):
    """One dense pass over every (check, rule) pair in ``jax.numpy``,
    ``block`` checks at a time, on the default device. Per check: the
    highest weight of any matching rule (-1 for none), which of
    ``DECISIONS`` a matching rule of that weight carries (N, D), and the
    lowest-numbered matching rule (-1 for none)."""
    import jax
    import jax.numpy as jnp
    lo = jnp.asarray(np.where(rules.bound, rules.lo, np.iinfo(np.int32).min
                              ).T.astype(np.int32))
    hi = jnp.asarray(np.where(rules.bound, rules.hi, np.iinfo(np.int32).max
                              ).T.astype(np.int32))
    w = jnp.asarray(rules.weight.astype(np.int32))
    kind = jnp.asarray(np.searchsorted(DECISIONS, rules.decision)
                       .astype(np.int32))
    n = len(vals)
    pad = (-n) % block
    v = np.concatenate([vals, np.zeros((pad, vals.shape[1]), vals.dtype)])
    v = jnp.asarray(v.astype(np.int32)).reshape(-1, block, vals.shape[1])

    @jax.jit
    def run(v):
        def one(vb):
            ok = jnp.ones((block, lo.shape[1]), bool)
            for k in range(lo.shape[0]):
                q = vb[:, k:k + 1]
                ok &= (q >= lo[k][None]) & (q <= hi[k][None])
            best = jnp.max(jnp.where(ok, w[None], -1), axis=1)
            tie = ok & (w[None] == best[:, None])
            carried = jnp.stack([jnp.any(tie & (kind[None] == d), axis=1)
                                 for d in range(len(DECISIONS))], axis=1)
            first = jnp.where(ok.any(axis=1), jnp.argmax(ok, axis=1), -1)
            return best, carried, first
        return jax.lax.map(one, v)

    best, carried, first = (np.asarray(a) for a in run(v))
    return (best.reshape(-1)[:n], carried.reshape(-1, len(DECISIONS))[:n],
            first.reshape(-1)[:n])


def best_weights(rules: Rules, vals: np.ndarray) -> np.ndarray:
    """Highest weight of any matching rule per check, -1 for none."""
    return dense_pass(rules, vals)[0]


def compare_answers(rules: Rules, vals: np.ndarray, decision: np.ndarray,
                    weight: np.ndarray) -> Dict[str, int]:
    """Mismatches between the (decision, weight) answered per check and
    the reference. An answer is right when its weight is the best of any
    matching rule and a rule of that weight carries its decision, or,
    where no rule matches, when both are negative."""
    best, carried, _ = dense_pass(rules, vals)
    none = best < 0
    kind = np.searchsorted(DECISIONS, decision)
    known = np.isin(decision, DECISIONS)
    ok_rule = known & (weight == best) & carried[
        np.arange(len(vals)), np.minimum(kind, len(DECISIONS) - 1)]
    ok_none = (decision < 0) & (weight < 0)
    bad = ~np.where(none, ok_none, ok_rule)
    return {"checks": int(len(vals)), "mismatches": int(bad.sum()),
            "matched": int((~none).sum())}


def first_match_answers(rules: Rules, vals: np.ndarray):
    """The control: (decision, weight) of the lowest-numbered matching
    rule, as a matcher that stops at its first hit answers. It breaks the
    guarantee that the most precise rule wins."""
    first = dense_pass(rules, vals)[2]
    hit = first >= 0
    idx = np.maximum(first, 0)
    return (np.where(hit, rules.decision[idx], -1),
            np.where(hit, rules.weight[idx], -1))


def compare(rules: Rules, vals: np.ndarray, decision: np.ndarray,
            weight: np.ndarray, rule_id: np.ndarray) -> Dict[str, int]:
    """Mismatches between the program's (decision, weight, rule_id) per
    check and the reference. A check is wrong when its weight is not the
    best, when its rule does not match it or carries another decision, or
    when it names no rule although one matches."""
    best = best_weights(rules, vals)
    none = best < 0
    has = rule_id >= 0
    rid = np.where(has, rule_id, 0)
    ok_rule = has & (rid < rules.n) & rule_matches(rules, np.minimum(
        rid, rules.n - 1), vals)
    ok_rule &= rules.weight[np.minimum(rid, rules.n - 1)] == best
    ok_rule &= rules.decision[np.minimum(rid, rules.n - 1)] == decision
    ok_rule &= weight == best
    ok_none = none & ~has & (decision < 0) & (weight < 0)
    bad = ~np.where(none, ok_none, ok_rule)
    return {"checks": int(len(vals)), "mismatches": int(bad.sum()),
            "matched": int((~none).sum())}

