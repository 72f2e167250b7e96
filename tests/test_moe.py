"""MoE: shard_map EP/TP paths vs dense reference (single-device mesh —
the collective code path with tp=1 groups)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod


@pytest.mark.parametrize("mode", ["ep", "tp"])
def test_moe_forward_matches_ref(mode):
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16,
                    capacity_factor=4.0, parallel_mode=mode)
    D = 8
    params = moe_mod.init_moe(jax.random.PRNGKey(0), D, cfg, "swiglu",
                              jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 6, D)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = moe_mod.moe_forward(params, x, cfg=cfg, act="swiglu", mesh=mesh,
                              batch_axes=("data",))
    ref = moe_mod.moe_ref(params, x, cfg=cfg, act="swiglu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens_gracefully():
    cfg = MoEConfig(num_experts=2, top_k=1, d_ff_expert=8,
                    capacity_factor=0.26, parallel_mode="ep")
    D = 4
    params = moe_mod.init_moe(jax.random.PRNGKey(1), D, cfg, "gelu",
                              jnp.float32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 32, D)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = moe_mod.moe_forward(params, x, cfg=cfg, act="gelu", mesh=mesh,
                              batch_axes=("data",))
    assert bool(jnp.isfinite(out).all())
    # with tight capacity some token outputs are zero (dropped)
    norms = jnp.linalg.norm(out.reshape(-1, D), axis=-1)
    assert float((norms == 0).mean()) > 0.1


def test_capacity_formula():
    from repro.models.moe import capacity_for
    assert capacity_for(65536, 128, 8, 1.25) == 640
    assert capacity_for(8, 128, 8, 1.25) >= 1
