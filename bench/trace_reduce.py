"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain form, which is also what the tests record:

    {"window_ns": [t0, t1],
     "devices": [{"modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns(, args)], ...]}

Device planes are the profiler's ``/device:TPU:<n>`` planes; their
``XLA Modules`` line holds one event per program run and ``XLA Ops`` one
per operation. Host events are every named span on the host's threads,
the benchmark's own ``bench.*`` annotations among them. All times share
the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def load(log_dir: str, window_ns: Optional[Sequence[float]] = None) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is not None:
                    d[key] = [[e.name, e.start_ns, e.duration_ns]
                              for e in line.events]
            devices.append(d)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    ev = [e.name, e.start_ns, e.duration_ns]
                    if e.name.startswith("bench."):
                        ev.append({k: v for k, v in e.stats
                                   if isinstance(v, (int, float, str))})
                    host.append(ev)
    if window_ns is None:
        ts = [e[1] for d in devices for e in d["modules"]] + \
            [e[1] for e in host]
        te = [e[1] + e[2] for d in devices for e in d["modules"]] + \
            [e[1] + e[2] for e in host]
        window_ns = [min(ts), max(te)] if ts else [0.0, 0.0]
    return {"window_ns": list(window_ns), "devices": devices, "host": host}


def bench_window(trace: dict, span: str = "bench.window") -> dict:
    """The same trace with its window set to the host span ``span``."""
    for name, s, d, *_ in trace["host"]:
        if name == span:
            return dict(trace, window_ns=[s, s + d])
    return trace


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(trace: dict, device: int) -> List[Interval]:
    lo, hi = trace["window_ns"]
    d = trace["devices"][device]
    events = d["modules"] or d["ops"]
    return union(_clip([(s, s + du) for _, s, du in events], lo, hi))


def window_s(trace: dict) -> float:
    lo, hi = trace["window_ns"]
    return (hi - lo) / 1e9


def busy_s(trace: dict) -> float:
    """Seconds in which some program ran, averaged over the devices."""
    if not trace["devices"]:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(trace, i))
              for i in range(len(trace["devices"])))
    return tot / len(trace["devices"]) / 1e9


_OP = re.compile(r"^(%?[\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")


def op_label(event_name: str) -> str:
    """``%fusion.3 = bf16[...]{...} fusion(...), kind=...`` ->
    ``%fusion.3 fusion``: the profiler names an operation by its whole
    HLO instruction."""
    m = _OP.match(event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:80]


def top_ops(trace: dict, k: int = 10) -> List[list]:
    """Device operations by total time in the window, summed over devices."""
    lo, hi = trace["window_ns"]
    tot: Dict[str, float] = {}
    for d in trace["devices"]:
        for name, s, du in d["ops"]:
            for a, b in _clip([(s, s + du)], lo, hi):
                key = op_label(name)
                tot[key] = tot.get(key, 0.0) + (b - a)
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def host_label(trace: dict, lo: float, hi: float) -> str:
    """What the host was doing in [lo, hi]: the shortest host span that
    covers at least half of it, else the one that overlaps it most."""
    best, best_cover, covering = "idle", 0.0, []
    for name, s, du, *_ in trace["host"]:
        ov = min(s + du, hi) - max(s, lo)
        if ov <= 0:
            continue
        if ov >= 0.5 * (hi - lo):
            covering.append((du, name))
        if ov > best_cover:
            best, best_cover = name, ov
    if covering:
        return min(covering)[1]
    return best


def idle_gaps(trace: dict, k: int = 10, device: int = 0) -> List[list]:
    """The ``k`` longest gaps with no program on ``device``, each named by
    what the host was doing in it."""
    lo, hi = trace["window_ns"]
    busy = busy_intervals(trace, device) if trace["devices"] else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_label(trace, a, b), (b - a) / 1e9] for a, b in gaps[:k]]


def module_runs(trace: dict, module: str) -> List[Interval]:
    """(start, end) of every run of the named program in the window."""
    lo, hi = trace["window_ns"]
    out = []
    for d in trace["devices"]:
        for name, s, du in d["modules"]:
            if _module_name(name) == module and s >= lo and s + du <= hi:
                out.append((s, s + du))
    return sorted(out)


def _module_name(event_name: str) -> str:
    """``jit_f(12)`` -> ``jit_f``: the profiler appends the program id."""
    return event_name.split("(")[0]


def host_spans(trace: dict, name: str) -> List[tuple]:
    """(start, end, args) of every host span called ``name``."""
    return sorted((e[1], e[1] + e[2], e[3] if len(e) > 3 else {})
                  for e in trace["host"] if e[0] == name)
