"""Compile for a described TPU v5e, with no chip attached: the Pallas
kernels at the widths of the registered configs that would call them, and
the qwen2-1.5b decode step and the serving engine's step around it at full
width (depth cut to 2 layers, and whole for the cache written in place),
and the granite-4.0-h-micro decode step whole.  What the chip's compiler
refuses, or cannot fit, fails here, at no chip time.

The topology is described only inside the fixture: only one process at a
time may load the TPU library, so no module may do it while it is imported.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import WIDTHS, kernel_case
from repro.kernels.flashattn.ops import attention
from repro.models import build_model, get_config
from repro.serve.engine import _shared_decode_step


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (an entry compiled for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", sorted(WIDTHS))
def test_kernel_compiles_for_v5e(v5e_chip, name):
    case = kernel_case(name)
    compiled = jax.jit(case.kernel).lower(*_on(v5e_chip, case.args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_backward_compiles_for_v5e(v5e_chip):
    """The train step's attention at smollm-360m's train shape (8 x 2048,
    15/5 heads of 64): forward with residuals, dq and dkv kernels."""
    q = jax.ShapeDtypeStruct((8, 2048, 15, 64), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((8, 2048, 5, 64), jnp.bfloat16,
                              sharding=v5e_chip)
    grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        attention(q, k, v).astype(jnp.float32)), (0, 1, 2)))
    text = grad.lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "2048,2048" not in text


def test_qwen2_decode_step_compiles_for_v5e(v5e_chip):
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(4, 1024))
    token = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        _on(v5e_chip, params), _on(v5e_chip, cache),
        {"token": _on(v5e_chip, token)}).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_qwen2_engine_step_hands_out_token_ids_for_v5e(v5e_chip):
    """The serving engine's greedy step for qwen2-1.5b at the serve cell's
    64 rows x 2048 (depth cut to 2): it chooses the token on the chip and
    hands out the [64] ids and the cache, with no [64, 1, 151936] logits."""
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(64, 2048))
    token = jax.ShapeDtypeStruct((64, 1), jnp.int32)
    lowered = _shared_decode_step(model, None).lower(
        _on(v5e_chip, params), _on(v5e_chip, cache),
        {"token": _on(v5e_chip, token)})
    ids, _ = lowered.out_info
    assert (ids.shape, ids.dtype) == ((64,), jnp.int32)
    mem = lowered.compile().memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert mem.output_size_in_bytes < cache_bytes + 1e6


def test_qwen2_engine_step_rewrites_the_cache_in_place_for_v5e(v5e_chip):
    """The serving engine's greedy step for qwen2-1.5b at full depth and the
    serve cell's 64 rows x 2048: the donated cache is aliased to the step's
    output, and no temporary holds a layer's slab or a second cache.  Each
    layer writes one position per row into the stacked keys and values, so
    no dynamic-update-slice rewrites the whole [28,64,2048,2,128] stack."""
    model = build_model(get_config("qwen2-1.5b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(64, 2048))
    token = jax.ShapeDtypeStruct((64, 1), jnp.int32)
    compiled = _shared_decode_step(model, None).lower(
        _on(v5e_chip, params), _on(v5e_chip, cache),
        {"token": _on(v5e_chip, token)}).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 1e9
    stack = "[28,64,2048,2,128]"
    assert not [line for line in compiled.as_text().splitlines()
                if "dynamic-update-slice(" in line and stack in line]


def test_granite_decode_step_fits_one_v5e(v5e_chip):
    """granite-4.0-h-micro at full width and depth, as the serve cell runs
    it: 32 rows of 2048 positions, the cache donated as ``ServeEngine``
    donates it.  The whole cache is rewritten in place, and the weights,
    the cache and the step's temporaries leave room on the chip's 16 GiB
    for the logits and the engine's row reset."""
    model = build_model(get_config("granite-4.0-h-micro"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(32, 2048))
    token = jax.ShapeDtypeStruct((32, 1), jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        _on(v5e_chip, params), _on(v5e_chip, cache),
        {"token": _on(v5e_chip, token)}).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes   # the chip pads ``index``
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes < 11e9
    assert mem.temp_size_in_bytes < 1e9         # the state is not copied
