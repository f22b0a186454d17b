"""The spline-epilogue subsystem, kernel to model.

Three layers of guarantees:
  * kernel vs oracle: every epilogue x block shape x odd shapes
    (exercising ops.py's padding path), element-wise and fused-GLU;
  * engine: with ``use_kernel=True`` every nonlinearity lowers to
    exactly ONE pallas_call (jaxpr inspection) and agrees with the jnp
    engine path to <=1e-5 in f32;
  * model: ``apply_mlp`` under ``fuse_mlp=True`` matches the unfused
    path to <=1e-4, gradients flow (custom-VJP recompute), and the
    step-builder rejects unfusable configs.
"""
import dataclasses

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ActivationConfig, ActivationEngine
from repro.kernels import epilogue as epi
from repro.kernels import ops, ref
from repro.models import layers
from repro.models.config import ModelConfig
from repro.parallel.partition import unbox_tree


def rand(shape, dtype=jnp.float32, scale=6.0, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.uniform(-scale, scale, shape), dtype)


def count_pallas_calls(jaxpr) -> int:
    """Recursively count the pallas_call eqns one execution runs (through
    pjit/custom_vjp/...). A cond runs one branch, so it counts as its
    busiest branch: the kernel builders put the compiled and the
    interpreted launch of the same kernel in the two branches of a
    platform-dependent cond."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        elif eqn.primitive.name == "cond":
            n += max(count_pallas_calls(b.jaxpr)
                     for b in eqn.params["branches"])
            continue
        for v in eqn.params.values():
            for sub in _subjaxprs_of(v):
                n += count_pallas_calls(sub)
    return n


def _subjaxprs_of(v):
    vals = v if isinstance(v, (tuple, list)) else (v,)
    for e in vals:
        if isinstance(e, jex_core.ClosedJaxpr):
            yield e.jaxpr
        elif isinstance(e, jex_core.Jaxpr):
            yield e


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

class TestElementwiseEpilogues:
    @pytest.mark.parametrize("act", epi.EPILOGUES)
    @pytest.mark.parametrize("blocks", [(epi.DEFAULT_BLOCK_ROWS,
                                         epi.DEFAULT_BLOCK_COLS), (8, 128)])
    @pytest.mark.parametrize("shape", [(8, 128), (3, 100), (257, 129),
                                       (4, 7, 64)])
    def test_kernel_matches_oracle(self, act, blocks, shape):
        x = rand(shape, seed=sum(shape))
        table = epi.table_for(act, 4.0, 32)
        y = ops.act(x, act, block_rows=blocks[0], block_cols=blocks[1])
        yr = ref.act_ref(x, act, table)
        assert y.shape == x.shape and y.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("act", epi.EPILOGUES)
    def test_bf16_passthrough(self, act):
        x = rand((16, 256), jnp.bfloat16, seed=3)
        y = ops.act(x, act)
        assert y.dtype == jnp.bfloat16
        yr = ref.act_ref(x, act, epi.table_for(act, 4.0, 32))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("fn", ["tanh", "sigmoid", "silu", "gelu_tanh",
                                    "softplus"])
    def test_scalar_input_matches_jnp_engine(self, fn):
        # regression: 0-d inputs crashed the kernel path's reshape
        keng = ActivationEngine(ActivationConfig(impl="cr", use_kernel=True))
        jeng = ActivationEngine(ActivationConfig(impl="cr"))
        x = jnp.float32(0.5)
        yk = getattr(keng, fn)(x)
        assert yk.shape == ()
        np.testing.assert_allclose(float(yk), float(getattr(jeng, fn)(x)),
                                   rtol=1e-5, atol=1e-6)

    def test_tanh_instance_is_cr_act(self):
        x = rand((32, 256), seed=5)
        np.testing.assert_array_equal(np.asarray(ops.act(x, "tanh")),
                                      np.asarray(ops.cr_act(x)))

    def test_grad_via_recompute_vjp(self):
        # custom-VJP backward = jnp recompute; check against the oracle's
        # own gradient
        x = rand((8, 128), scale=2.0, seed=7)
        table = epi.table_for("silu", 4.0, 32)
        g = jax.grad(lambda v: ops.act(v, "silu").sum())(x)
        gr = jax.grad(lambda v: ref.act_ref(v, "silu", table).sum())(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-5, atol=1e-6)


class TestFusedGluEpilogues:
    @pytest.mark.parametrize("act", epi.EPILOGUES)
    @pytest.mark.parametrize("mkn", [(8, 128, 128), (16, 700, 130),
                                     (130, 512, 256)])
    def test_kernel_matches_oracle(self, act, mkn):
        m, k, n = mkn
        x = rand((m, k), scale=1.0, seed=m + n)
        wg = rand((k, n), scale=0.05, seed=k)
        wu = rand((k, n), scale=0.05, seed=k + 1)
        table = epi.table_for(act, 4.0, 32)
        y = ops.fused_glu(x, wg, wu, act=act)
        yr = ref.fused_glu_ref(x, wg, wu, table, act=act)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("act", ["silu", "softplus"])
    def test_lookup_strategies_agree(self, act):
        # the kernel's in-kernel select chain against the XLA ``take``
        # recompute of its custom-VJP backward (depth 32 and, for the
        # softplus residual, depth 64)
        x = rand((16, 256), scale=1.0, seed=11)
        wg = rand((256, 128), scale=0.05, seed=12)
        wu = rand((256, 128), scale=0.05, seed=13)
        spec = epi._spec_for_epilogue(act, "cr_spline", 4.0, 32)
        y = ops.fused_glu(x, wg, wu, act=act)
        yr = ops._fused_glu_ref_math(
            (spec, act), x, wg, wu,
            jnp.asarray(epi.params_for(act, spec), jnp.float32))
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)

    def test_grads_flow_through_fused(self):
        x = rand((8, 256), scale=0.5, seed=17)
        wg = rand((256, 128), scale=0.05, seed=18)
        wu = rand((256, 128), scale=0.05, seed=19)
        table = epi.table_for("silu", 4.0, 32)

        def fused(x, wg, wu):
            return ops.fused_glu(x, wg, wu, act="silu").sum()

        def unfused(x, wg, wu):
            return ref.fused_glu_ref(x, wg, wu, table, act="silu").sum()

        g = jax.grad(fused, argnums=(0, 1, 2))(x, wg, wu)
        gr = jax.grad(unfused, argnums=(0, 1, 2))(x, wg, wu)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: one pallas_call per nonlinearity
# ---------------------------------------------------------------------------

class TestEngineSinglePass:
    ENGINE_FNS = ("tanh", "sigmoid", "silu", "gelu_tanh", "softplus")

    @pytest.mark.parametrize("fn", ENGINE_FNS)
    def test_single_pallas_call_and_jnp_agreement(self, fn):
        kcfg = ActivationConfig(impl="cr", depth=32, use_kernel=True)
        jcfg = dataclasses.replace(kcfg, use_kernel=False)
        keng, jeng = ActivationEngine(kcfg), ActivationEngine(jcfg)
        x = rand((16, 384), seed=23)

        jaxpr = jax.make_jaxpr(getattr(keng, fn))(x)
        assert count_pallas_calls(jaxpr.jaxpr) == 1, jaxpr

        yk = getattr(keng, fn)(x)
        yj = getattr(jeng, fn)(x)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yj),
                                   rtol=1e-5, atol=1e-5)

    def test_non_approximant_engine_ignores_use_kernel(self):
        # taylor/region/base2 have no approximant scheme (and therefore
        # no epilogue kernel): use_kernel must not reroute them
        eng = ActivationEngine(ActivationConfig(impl="taylor",
                                                use_kernel=True))
        x = rand((4, 128), seed=29)
        assert count_pallas_calls(jax.make_jaxpr(eng.sigmoid)(x).jaxpr) == 0

    @pytest.mark.parametrize("impl", ["pwl", "poly", "rational"])
    def test_non_cr_schemes_kernelize_every_nonlinearity(self, impl):
        # under the Approximant API every registered scheme lowers each
        # nonlinearity to exactly ONE pallas_call, like the CR flagship
        eng = ActivationEngine(ActivationConfig(impl=impl, use_kernel=True))
        x = rand((4, 128), seed=29)
        for fn in ("tanh", "sigmoid", "silu", "gelu_tanh"):
            jaxpr = jax.make_jaxpr(getattr(eng, fn))(x)
            assert count_pallas_calls(jaxpr.jaxpr) == 1, (impl, fn)


# ---------------------------------------------------------------------------
# model: fused vs unfused apply_mlp
# ---------------------------------------------------------------------------

def _mlp_setup(mlp_act="silu", glu=True, impl="cr"):
    cfg = ModelConfig(d_model=64, d_ff=256, glu=glu, mlp_act=mlp_act,
                      compute_dtype="float32",
                      activation=ActivationConfig(impl=impl, depth=32))
    boxed = layers.init_mlp(jax.random.key(0), cfg)
    params, _ = unbox_tree(boxed)
    x = rand((2, 16, 64), scale=0.5, seed=31)
    return cfg, params, x


class TestFusedMlp:
    @pytest.mark.parametrize("mlp_act", ["silu", "gelu_tanh", "tanh"])
    def test_fused_matches_unfused(self, mlp_act):
        cfg, params, x = _mlp_setup(mlp_act)
        fcfg = dataclasses.replace(cfg, fuse_mlp=True)
        eng = ActivationEngine(cfg.activation)
        assert layers.mlp_fusable(fcfg, eng)
        y_unfused = layers.apply_mlp(params, x, cfg, eng)
        y_fused = layers.apply_mlp(params, x, fcfg, eng)
        np.testing.assert_allclose(np.asarray(y_fused),
                                   np.asarray(y_unfused),
                                   rtol=1e-4, atol=1e-4)

    def test_fused_grads_match_unfused(self):
        cfg, params, x = _mlp_setup()
        fcfg = dataclasses.replace(cfg, fuse_mlp=True)
        eng = ActivationEngine(cfg.activation)

        def loss(p, c):
            return (layers.apply_mlp(p, x, c, eng) ** 2).sum()

        g = jax.grad(loss)(params, fcfg)
        gr = jax.grad(loss)(params, cfg)
        for k in params:
            np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                       rtol=1e-3, atol=1e-3, err_msg=k)

    def test_not_fusable_without_glu_or_cr(self):
        cfg, _, _ = _mlp_setup(glu=False)
        fcfg = dataclasses.replace(cfg, fuse_mlp=True)
        assert not layers.mlp_fusable(fcfg, ActivationEngine(cfg.activation))
        cfg2, _, _ = _mlp_setup(impl="exact")
        fcfg2 = dataclasses.replace(cfg2, fuse_mlp=True)
        assert not layers.mlp_fusable(fcfg2,
                                      ActivationEngine(cfg2.activation))

    def test_step_builder_rejects_unfusable_config(self):
        from repro.launch import steps
        cfg = ModelConfig(glu=False, fuse_mlp=True,
                          activation=ActivationConfig(impl="cr"))
        with pytest.raises(ValueError, match="fuse_mlp"):
            steps.make_train_step(cfg)


class TestFusedDeploymentEntryPoints:
    def test_fused_of_every_arch_passes_step_validation(self):
        # the advertised deployment wrapper must always produce a config
        # the step builders accept (fused or honestly left unfused)
        from repro.configs import registry
        from repro.configs.common import fused_of
        from repro.launch import steps
        for arch in registry.assigned_archs():
            cfg = fused_of(registry.get(arch, smoke=True))
            steps.make_train_step(cfg)  # must not raise
            if cfg.fuse_mlp:
                assert cfg.activation.impl == "cr"
                assert cfg.activation.use_kernel

    def test_fused_of_identity_when_nothing_to_fuse(self):
        from repro.configs.common import fused_of
        no_glu = ModelConfig(glu=False)
        assert fused_of(no_glu) is no_glu
        no_ffn = ModelConfig(d_ff=0, n_heads=0, use_mamba=True)
        assert fused_of(no_ffn) is no_ffn
        odd_act = ModelConfig(glu=True, mlp_act="relu2")
        assert fused_of(odd_act) is odd_act

    def test_cr_act_kernel_config_is_kernelized(self):
        from repro.configs.common import CR_ACT_KERNEL
        eng = ActivationEngine(CR_ACT_KERNEL)
        assert eng._kernelized
        x = rand((8, 128), seed=41)
        assert count_pallas_calls(jax.make_jaxpr(eng.silu)(x).jaxpr) == 1


class TestSubsystemLayout:
    def test_single_cr_block_definition(self):
        # the acceptance-criteria grep, as a test: exactly one definition
        # of the CR-tanh block / f32 basis, owned by epilogue.py
        import pathlib
        kdir = pathlib.Path(layers.__file__).parents[1] / "kernels"
        defs = []
        for f in kdir.glob("*.py"):
            for i, line in enumerate(f.read_text().splitlines(), 1):
                if line.startswith("def _cr_tanh_block") or \
                        line.startswith("def _basis_weights_f32"):
                    defs.append((f.name, i))
        assert [d[0] for d in defs] == ["epilogue.py", "epilogue.py"], defs

    def test_thin_instances_import_shared_block(self):
        from repro.kernels import cr_act, fused_glu
        assert cr_act._cr_tanh_block is epi._cr_tanh_block
        assert fused_glu._cr_tanh_block is epi._cr_tanh_block
