"""What the MCT host executor was doing while the device waited.

Every instant of the traced window in which device 0 runs no program is
charged to the first of these that holds, read from the program's own
``mct.*`` spans (``core/wrapper.MCTWrapper``, ``ErbiumEngine.match``):

    compile   some ``mct.compile`` is open
    encode    some ``mct.encode`` is open
    stage     some other ``mct.*`` span is open (``mct.execute`` among
              them: Python between two stages counts here)
    no_batch  none is open: no worker held a batch

The four shares sum to the device's idle share of the window.

The profiler records a span only if it opens and closes while it runs, so
a batch open when the trace starts or stops loses its ``mct.execute``,
and keeps only the stages that opened and closed inside. Such a stage,
outside every recorded ``mct.execute``, still shows a batch held: a
``mct.collect`` (a batch's last stage) from the window's start to its
end, a ``mct.encode`` (its first) from its start to the window's end.
That time counts as "stage": the compile or encode of a batch open at
either end is not told apart from its other stages.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from bench import trace_reduce

Interval = trace_reduce.Interval
PREFIX = "mct."


def _minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` less ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _length(iv: List[Interval]) -> float:
    return sum(e - s for s, e in iv)


def _held(spans, lo: float, hi: float) -> List[Interval]:
    """Where some worker held a batch: every ``mct.*`` span, and the
    batches open at either end of the trace, read from the stages they
    kept; a kept stage that neither end explains is of a batch open at
    both ends."""
    execute = PREFIX + "execute"
    batches = [(s, e) for n, s, e in spans if n == execute]
    kept = [(n, s, e) for n, s, e in spans if n != execute and not any(
        a <= s and e <= b for a, b in batches)]
    cut = [(lo, e) for n, s, e in kept if n == PREFIX + "collect"] + \
        [(s, hi) for n, s, e in kept if n == PREFIX + "encode"]
    if any(not any(a <= s and e <= b for a, b in cut) for _, s, e in kept):
        cut.append((lo, hi))
    return trace_reduce.union(
        [(max(s, lo), min(e, hi)) for s, e in cut + [sp[1:] for sp in spans]
         if e > lo and s < hi])


def shares(trace: Optional[dict], device: int = 0
           ) -> Optional[Dict[str, float]]:
    """Each part's share of the traced window, in %; None where the trace
    has no device or no ``mct.*`` span."""
    if trace is None or not trace["devices"]:
        return None
    lo, hi = trace["window_ns"]
    spans = [(name, s, s + du) for name, s, du, *_ in trace["host"]
             if name.startswith(PREFIX)]
    if not spans or hi <= lo:
        return None

    def open_(names) -> List[Interval]:
        return trace_reduce.union(
            [(max(s, lo), min(e, hi)) for n, s, e in spans
             if n in names and e > lo and s < hi])

    left = _minus([(lo, hi)], trace_reduce.busy_intervals(trace, device))
    out = {}
    for part, held in (("compile", open_({PREFIX + "compile"})),
                       ("encode", open_({PREFIX + "encode"})),
                       ("stage", _held(spans, lo, hi))):
        rest = _minus(left, held)
        out[part] = 100.0 * (_length(left) - _length(rest)) / (hi - lo)
        left = rest
    out["no_batch"] = 100.0 * _length(left) / (hi - lo)
    return out
