"""Compiles for a described TPU v5e chip: the kernels and the offloaded
block programs at real widths.

Nothing runs: each program is lowered from shapes and compiled by the TPU
compiler installed here, for a chip that is described and not attached.
That catches what interpret mode cannot (a Mosaic layout or memory-space
the chip refuses, a program that does not fit the device) at no chip time.
The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU library; where it cannot be described, the tests
skip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_adam import fused_adam_pallas
from repro.kernels.overflow_check import overflow_check_pallas
from repro.models.transformer import (apply_layer, ffn_kind,
                                      init_layer_params, mixer_kind)

BATCH, SEQ = 4, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_overflow_check_compiles(one_chip, dtype):
    x = _on(one_chip, (4_000_001,), dtype)
    compiled = jax.jit(overflow_check_pallas).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_adam_compiles(one_chip):
    # one Adam subgroup: Qwen2.5-0.5B's FFN up projection (896 x 4864)
    shape = (896, 4864)
    p, g, m, v = (_on(one_chip, shape, jnp.float32) for _ in range(4))
    step = _on(one_chip, (), jnp.int32)
    compiled = jax.jit(fused_adam_pallas).lower(p, g, m, v, step).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen_block(one_chip):
    """Qwen2.5-0.5B's block apply and its bf16 parameter and activation
    shapes (compute precision, as the offload session stages them)."""
    cfg = get_config("qwen2.5-0.5b")
    kinds = (mixer_kind(cfg, 0), ffn_kind(cfg, 0))
    shapes = jax.eval_shape(
        lambda k: init_layer_params(k, cfg, 0), jax.random.PRNGKey(0))
    params = {k: _on(one_chip, s.shape, jnp.bfloat16)
              for k, s in shapes.items()}
    h = _on(one_chip, (BATCH, SEQ, cfg.d_model), jnp.bfloat16)

    def block_apply(p, x):
        return apply_layer(cfg, kinds, p, x)[0]

    return block_apply, params, h


def test_block_forward_compiles(qwen_block):
    block_apply, params, h = qwen_block
    compiled = jax.jit(block_apply).lower(params, h).compile()
    out = compiled.out_info
    assert out.shape == h.shape and out.dtype == h.dtype


def test_block_backward_compiles(qwen_block):
    block_apply, params, h = qwen_block

    def block_bwd(p, x, dy):
        _, vjp = jax.vjp(block_apply, p, x)
        return vjp(dy)

    compiled = jax.jit(block_bwd).lower(params, h, h).compile()
    dparams, dx = compiled.out_info
    assert dx.shape == h.shape
    assert {k: v.shape for k, v in dparams.items()} == \
        {k: v.shape for k, v in params.items()}
