"""Chunkwise mLSTM / sLSTM / mamba vs sequential step oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs.base import SSMConfig
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod


@pytest.mark.parametrize("T,chunk", [(16, 4), (17, 8), (32, 32), (7, 16)])
def test_mlstm_chunkwise_matches_sequential(T, chunk):
    rng = np.random.default_rng(T)
    B, D, H = 2, 32, 4
    params = xlstm_mod.init_mlstm(jax.random.PRNGKey(0), D, H, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    out_c = xlstm_mod.mlstm_forward(params, x, n_heads=H, chunk=chunk)
    out_s = xlstm_mod.mlstm_ref(params, x, n_heads=H)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_s),
                               rtol=2e-4, atol=2e-4)


def test_slstm_forward_matches_steps():
    rng = np.random.default_rng(1)
    B, T, D, H = 2, 9, 16, 2
    params = xlstm_mod.init_slstm(jax.random.PRNGKey(1), D, H, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    full = xlstm_mod.slstm_forward(params, x, n_heads=H)
    st = xlstm_mod.slstm_init_state(B, H, D // H)
    outs = []
    for t in range(T):
        y, st = xlstm_mod.slstm_step(params, x[:, t:t + 1], st, n_heads=H)
        outs.append(y)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,chunk", [(12, 4), (16, 16), (9, 8)])
def test_mamba_chunked_matches_stepwise(T, chunk):
    rng = np.random.default_rng(T + 100)
    B, D = 2, 16
    cfg = SSMConfig(state_dim=8, d_inner_mult=2, conv_width=4, chunk=chunk)
    params = ssm_mod.init_mamba(jax.random.PRNGKey(2), D, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    full = ssm_mod.mamba_forward(params, x, cfg=cfg)
    step = ssm_mod.mamba_ref(params, x, cfg=cfg)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,chunk", [(12, 4), (16, 16), (9, 8)])
def test_mamba_chunk_local_matches_baseline(T, chunk):
    """The memory-optimised (chunk-local) path is numerically identical."""
    import dataclasses
    rng = np.random.default_rng(T + 200)
    B, D = 2, 16
    cfg = SSMConfig(state_dim=8, d_inner_mult=2, conv_width=4, chunk=chunk)
    cfg_cl = dataclasses.replace(cfg, chunk_local=True)
    params = ssm_mod.init_mamba(jax.random.PRNGKey(4), D, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    base = ssm_mod.mamba_forward(params, x, cfg=cfg)
    cl = ssm_mod.mamba_forward(params, x, cfg=cfg_cl)
    np.testing.assert_allclose(np.asarray(cl), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_slstm_local_grad_matches_plain():
    """Custom-VJP (single-psum) sLSTM: values AND grads match the plain
    GSPMD path."""
    B, T, D, H = 2, 9, 16, 2
    params = xlstm_mod.init_slstm(jax.random.PRNGKey(1), D, H, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    def loss_plain(p, x):
        return jnp.sum(xlstm_mod.slstm_forward(p, x, n_heads=H) ** 2)

    def loss_lg(p, x):
        return jnp.sum(xlstm_mod.slstm_forward_sharded(
            p, x, n_heads=H, mesh=mesh, batch_axes=("data",)) ** 2)

    l1, g1 = jax.value_and_grad(loss_plain)(params, x)
    l2, g2 = jax.value_and_grad(loss_lg)(params, x)
    assert abs(float(l1) - float(l2)) < 1e-5
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-5, atol=1e-6)


def test_mamba_prefill_state_matches_stepped_state():
    rng = np.random.default_rng(7)
    B, T, D = 1, 11, 8
    cfg = SSMConfig(state_dim=4, d_inner_mult=2, conv_width=4, chunk=4)
    params = ssm_mod.init_mamba(jax.random.PRNGKey(3), D, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, T, D)) * 0.5, jnp.float32)
    st_pre = ssm_mod.mamba_prefill_state(params, x, cfg=cfg)
    st = ssm_mod.mamba_init_state(params, B)
    for t in range(T):
        _, st = ssm_mod.mamba_step(params, x[:, t:t + 1], st, cfg=cfg)
    np.testing.assert_allclose(np.asarray(st_pre.h), np.asarray(st.h),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_pre.conv),
                               np.asarray(st.conv), rtol=2e-4, atol=2e-4)
