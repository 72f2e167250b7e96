"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles for a v5e that is only described. That refuses, here,
what the chip would refuse: kernel tilings, fast-memory overuse, programs
that do not fit the device's HBM. The topology is described inside a
fixture, never at import, so every test worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.rule_match import rule_match_pallas
from repro.models.registry import build_model

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_rule_match_kernel_compiles_at_paper_width(one_chip):
    """MCT v2 (31 columns), 160k rules padded to tile_r, B=2048."""
    C, B, R = 31, 2048, 160_256
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = (sds((C, B), jnp.int32), sds((C, R), jnp.int32),
            sds((C, R), jnp.int32), sds((1, R), jnp.int32))
    fn = jax.jit(functools.partial(rule_match_pallas, tile_b=256,
                                   tile_r=512, interpret=False))
    lowered = fn.lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_llama3_2_3b_decode_step_fits_one_chip(one_chip):
    """Full-width llama3.2-3b decode step at B=8, max_seq=128."""
    cfg = get_config("llama3.2-3b")
    model = build_model(cfg)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        place, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree_util.tree_map(place, model.cache_struct(8, 128))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, i: model.decode_step(p, c, t, i),
                   donate_argnums=(1,))
    mem = step.lower(params, cache, tok, pos).compile().memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6 * 10**9      # bf16 weights
    assert held < V5E_HBM_BYTES, held
