"""Compile rehearsals for a TPU v5e: the epilogue kernels at qwen3-0.6b
widths, compiled by the TPU compiler for a described (not attached)
chip. Interpret mode cannot see what Mosaic refuses — an unlowerable
gather, a one-hot operand past the scoped-VMEM limit — so these compiles
guard the kernels of the serving path at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and a worker that
fails to would otherwise collect different tests from the others.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_MODEL, D_FF = 1024, 3072              # qwen3-0.6b
DECODE_M, PREFILL_M = 8, 4096           # 8 slots; 8 prompts x 512 tokens
SCHEMES = ("cr_spline", "pwl", "poly", "rational")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a TPU compile is written to the persistent cache but cannot be read
    # back without a chip; keep the cache out of these compiles entirely
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_elementwise_compiles(scheme, m, one_chip, no_compile_cache):
    text = _compiled_text(lambda x: ops.act(x, "silu", method=scheme),
                          [(m, D_FF)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_elementwise_softplus_depth64_compiles(m, one_chip,
                                               no_compile_cache):
    # the softplus residual widens the table to depth 64
    text = _compiled_text(lambda x: ops.act(x, "softplus"), [(m, D_FF)],
                          one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_glu_compiles(scheme, m, one_chip, no_compile_cache):
    text = _compiled_text(
        lambda x, wg, wu: ops.fused_glu(x, wg, wu, act="silu", method=scheme),
        [(m, D_MODEL), (D_MODEL, D_FF), (D_MODEL, D_FF)], one_chip)
    assert "tpu_custom_call" in text
