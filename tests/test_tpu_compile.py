"""Compile rehearsals for a TPU v5e: the epilogue kernels at qwen3-0.6b
widths, compiled by the TPU compiler for a described (not attached)
chip. Interpret mode cannot see what Mosaic refuses — an unlowerable
gather, a one-hot operand past the scoped-VMEM limit — so these compiles
guard the kernels of the serving path at no chip time. The paged decode
chunk is compiled the same way, to check that the KV page pool is
updated in place rather than copied.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and a worker that
fails to would otherwise collect different tests from the others.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import ops
from repro.models import model as M
from repro.serve.engine import make_decode_chunk

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


# the qwen3-0.6b.chat serving shapes: 32 slots, max_len 2560, a pool of
# 3270 pages of 16 tokens, decode chunks of 8 in-jit steps
SLOTS, MAX_LEN, N_PAGES, PAGE, CHUNK, CELL_LAYERS = 32, 2560, 3270, 16, 8, 28


def _compile_paged_decode(n_layers, sharding):
    """The paged decode chunk of qwen3-0.6b at ``n_layers``, compiled from
    abstract bf16 shapes and donated as ServeEngine donates it."""
    cfg = registry.get("qwen3-0.6b", n_layers=n_layers)

    def sds(s, dtype=None):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                    sharding=sharding)

    params = jax.tree.map(
        lambda s: sds(s, jnp.bfloat16
                      if jnp.issubdtype(s.dtype, jnp.floating) else None),
        M.abstract_params(cfg)[0])
    cache = jax.tree.map(sds, M.paged_cache_spec(cfg, SLOTS, N_PAGES, PAGE,
                                                 MAX_LEN))
    vec = lambda dtype: jax.ShapeDtypeStruct((SLOTS,), dtype,
                                             sharding=sharding)
    state = {"tok": vec(jnp.int32), "uid": vec(jnp.int32),
             "emitted": vec(jnp.int32), "active": vec(jnp.bool_),
             "budget": vec(jnp.int32), "temp": vec(jnp.float32),
             "eos": vec(jnp.int32),
             "key": sds(jax.eval_shape(lambda: jax.random.key(0)))}
    decode = jax.jit(make_decode_chunk(cfg, CHUNK, paged=True),
                     donate_argnums=(1, 2))
    compiled = decode.lower(params, cache, state).compile()
    k = cache["layers"]["k"]
    pool_bytes = 2 * k.size * k.dtype.itemsize
    return compiled, k.shape, pool_bytes


def test_paged_decode_updates_pool_in_place(one_chip, no_compile_cache):
    """The pool rides the layer scan's carry: no copy or
    dynamic-update-slice of the whole stacked pool, and temporaries that
    do not grow with it. The scan body compiles once whatever the depth,
    so temp is affine in the layer count: two shallow compiles give its
    value at the cell's 28 layers, which must stay under a tenth of the
    28-layer pool (a pool threaded as scan xs/ys gives more than the
    pool itself)."""
    (c2, shape2, pool2), (c4, _, pool4) = (
        _compile_paged_decode(n, one_chip) for n in (2, 4))
    t2 = c2.memory_analysis().temp_size_in_bytes
    t4 = c4.memory_analysis().temp_size_in_bytes
    per_layer_temp = (t4 - t2) / 2
    per_layer_pool = (pool4 - pool2) / 2
    temp_cell = t2 + (CELL_LAYERS - 2) * per_layer_temp
    pool_cell = pool2 + (CELL_LAYERS - 2) * per_layer_pool
    assert temp_cell < 0.1 * pool_cell, (temp_cell, pool_cell)

    stacked = "bf16[%s]" % ",".join(map(str, shape2))
    moved = [line.strip()[:160] for line in c2.as_text().splitlines()
             if f"= {stacked}" in line
             and (" copy(" in line or "dynamic-update-slice" in line)]
    assert not moved, moved
