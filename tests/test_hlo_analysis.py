"""HLO static analyzer: cross-validation vs XLA cost_analysis and analytic
FLOP counts; while-loop trip-count multiplication; collective extraction."""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_text


def _analyze(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return analyze_text(comp.as_text()), comp


def _xla_cost(comp):
    # jaxlib returns a dict on some versions, a one-element list of dicts
    # (one per computation) on others
    ca = comp.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca


def test_matmul_flops_match_xla():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    mine, comp = _analyze(lambda a, b: a @ b, x, w)
    xla = _xla_cost(comp)
    assert abs(mine["flops"] - xla["flops"]) / xla["flops"] < 0.02
    assert abs(mine["flops"] - 2 * 128 * 256 * 512) / mine["flops"] < 0.02


def test_scan_trip_count_multiplied():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, ws)[0]

    mine, comp = _analyze(f, x, ws)
    analytic = 10 * (2 * 64 * 64 * 64)
    assert mine["flops"] >= analytic
    assert mine["flops"] <= analytic * 1.2
    # XLA undercounts by ~trip count
    assert _xla_cost(comp)["flops"] < mine["flops"] / 5


def test_nested_scan_trip_counts():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 4, 32, 32), jnp.float32)

    def f(x, ws):
        def outer(c, wg):
            def inner(ci, w):
                return ci @ w, None
            return jax.lax.scan(inner, c, wg)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    mine, _ = _analyze(f, x, ws)
    analytic = 12 * 2 * 32 * 32 * 32
    assert abs(mine["flops"] - analytic) / analytic < 0.2


def test_elementwise_bytes_reasonable():
    x = jax.ShapeDtypeStruct((1 << 16,), jnp.float32)
    mine, comp = _analyze(lambda a: jnp.exp(a) + 1.0, x)
    # one read + one write at fusion granularity ~ 512 KiB
    assert 2 * 4 * (1 << 16) * 0.5 < mine["bytes"] < 2 * 4 * (1 << 16) * 3


def test_collectives_extracted(monkeypatch):
    import subprocess, sys, textwrap, json
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
        import sys; sys.path.insert(0, "src")
        from repro.launch.hlo_analysis import analyze_text
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        def f(x, w):
            y = x @ w
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("data", None)))
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        with mesh:
            comp = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P("data", None)),
                NamedSharding(mesh, P(None, "model")))).lower(x, w).compile()
        r = analyze_text(comp.as_text())
        print(json.dumps({"cb": r["collective_bytes"],
                          "wire": r["collective_wire_bytes"],
                          "np": r["num_partitions"]}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(Path(__file__).resolve().parents[1]))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["np"] == 8
    assert sum(res["cb"].values()) > 0
    assert res["wire"] > 0
