"""Run one benchmark cell once on the accelerator JAX finds here.

    python3 bench/run.py --workload hymba-1.5b.de_steady --seed 1 \\
        --seconds 10 --trace 0

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json`` at the root of the checkout. The run exits non-zero
and prints no result unless JAX's first device is a TPU and there are as
many chips as the cell asks for. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: every
number compared with the reference, beside its limit. The same checks
are the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    spec = harness.load_spec(ROOT)
    cell = harness.cell_of(spec, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX's first device is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[cache] dir={enable_compile_cache()}", flush=True)

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    log = result.pop("_log")
    print("[run] " + " ".join(f"{k}={v}" for k, v in log.items()),
          flush=True)
    for name, c in result["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}"
              f"{' (at least)' if c.get('at_least') else ''}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
