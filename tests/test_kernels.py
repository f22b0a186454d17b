"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode — kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.activations import tanh_table
from repro.kernels import ops
from repro.kernels.ref import cr_act_ref, fused_glu_ref

TAB32 = tanh_table(4.0, 32)
TAB8 = tanh_table(4.0, 8)
TAB64 = tanh_table(4.0, 64)


def rand(shape, dtype, scale=6.0, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.uniform(-scale, scale, shape), dtype)


class TestCrAct:
    @pytest.mark.parametrize("shape", [
        (8, 128), (32, 512), (64, 384), (1, 128), (3, 100), (257, 129),
        (4, 7, 64), (2, 3, 5, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shape_dtype_sweep(self, shape, dtype):
        x = rand(shape, dtype)
        y = ops.cr_act(x, TAB32)
        yr = cr_act_ref(x, TAB32)
        assert y.shape == x.shape and y.dtype == x.dtype
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(yr, np.float32),
            rtol=1e-5, atol=1e-6)

    # the in-kernel select chain at every table depth, on two block grids
    @pytest.mark.parametrize("blocks", [(8, 128), (32, 256)])
    @pytest.mark.parametrize("table", [TAB8, TAB32, TAB64])
    def test_lookup_strategies_and_depths(self, blocks, table):
        x = rand((32, 256), jnp.float32, seed=1)
        y = ops.cr_act(x, table, block_rows=blocks[0], block_cols=blocks[1])
        yr = cr_act_ref(x, table)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-6)

    def test_block_shape_invariance(self):
        x = rand((64, 1024), jnp.float32, seed=2)
        y1 = ops.cr_act(x, TAB32, block_rows=8, block_cols=128)
        y2 = ops.cr_act(x, TAB32, block_rows=64, block_cols=512)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-7)

    def test_matches_exact_tanh_to_paper_bound(self):
        x = rand((16, 256), jnp.float32, scale=3.9, seed=3)
        y = ops.cr_act(x, TAB32)
        assert float(jnp.max(jnp.abs(y - jnp.tanh(x)))) < 1e-4

    @given(rows=st.integers(1, 70), cols=st.integers(1, 300))
    @settings(max_examples=12, deadline=None)
    def test_padding_property(self, rows, cols):
        x = rand((rows, cols), jnp.float32, seed=rows * 1000 + cols)
        y = ops.cr_act(x, TAB32)
        yr = cr_act_ref(x, TAB32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-6)

    def test_saturation_and_sign(self):
        x = jnp.asarray([[-100.0, -4.0, 0.0, 4.0, 100.0] * 26], jnp.float32)
        y = np.asarray(ops.cr_act(x, TAB32))[0]
        sat = TAB32.saturation
        assert y[0] == pytest.approx(-sat) and y[4] == pytest.approx(sat)
        assert y[2] == pytest.approx(0.0, abs=1e-7)


class TestFusedGlu:
    @pytest.mark.parametrize("m,k,n", [
        (8, 128, 128), (48, 256, 192), (128, 512, 256), (16, 700, 130),
        (130, 512, 512),
    ])
    @pytest.mark.parametrize("act", ["silu", "gelu_tanh", "tanh"])
    def test_shape_act_sweep(self, m, k, n, act):
        x = rand((m, k), jnp.float32, scale=1.0, seed=m + n)
        wg = rand((k, n), jnp.float32, scale=0.05, seed=k)
        wu = rand((k, n), jnp.float32, scale=0.05, seed=k + 1)
        y = ops.fused_glu(x, wg, wu, TAB32, act=act)
        yr = fused_glu_ref(x, wg, wu, TAB32, act=act)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-5)

    def test_bf16(self):
        x = rand((32, 256), jnp.bfloat16, scale=1.0, seed=7)
        wg = rand((256, 128), jnp.bfloat16, scale=0.05, seed=8)
        wu = rand((256, 128), jnp.bfloat16, scale=0.05, seed=9)
        y = ops.fused_glu(x, wg, wu, TAB32)
        yr = fused_glu_ref(x, wg, wu, TAB32)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_block_shape_invariance(self):
        x = rand((64, 512), jnp.float32, scale=1.0, seed=10)
        wg = rand((512, 256), jnp.float32, scale=0.05, seed=11)
        wu = rand((512, 256), jnp.float32, scale=0.05, seed=12)
        y1 = ops.fused_glu(x, wg, wu, TAB32, block_m=8, block_n=128, block_k=128)
        y2 = ops.fused_glu(x, wg, wu, TAB32, block_m=64, block_n=256, block_k=512)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-6)

    def test_3d_batch(self):
        x = rand((4, 16, 256), jnp.float32, scale=1.0, seed=13)
        wg = rand((256, 128), jnp.float32, scale=0.05, seed=14)
        wu = rand((256, 128), jnp.float32, scale=0.05, seed=15)
        y = ops.fused_glu(x, wg, wu, TAB32)
        yr = fused_glu_ref(x, wg, wu, TAB32)
        assert y.shape == (4, 16, 128)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-5)

    def test_matches_exact_swiglu(self):
        # end to end vs jax.nn silu swiglu: error bounded by the spline error
        x = rand((16, 256), jnp.float32, scale=0.3, seed=16)
        wg = rand((256, 128), jnp.float32, scale=0.05, seed=17)
        wu = rand((256, 128), jnp.float32, scale=0.05, seed=18)
        y = ops.fused_glu(x, wg, wu, TAB32, act="silu")
        exact = jax.nn.silu(x @ wg) * (x @ wu)
        assert float(jnp.max(jnp.abs(y - exact))) < 5e-4
