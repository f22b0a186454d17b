"""Quickstart: the paper's technique in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. Build the paper's Catmull-Rom tanh engine and compare it to exact tanh
   and the PWL baseline (paper Tables I/II, one row).
2. Run the bit-accurate Q2.13 hardware datapath (paper Fig. 3).
3. Drop the engine into a transformer block: one forward+backward step of
   a small LLaMA-family model where EVERY nonlinearity (SwiGLU's SiLU)
   runs through the spline unit.
4. Call the Pallas TPU kernel (interpret mode on CPU) and check it against
   the pure-jnp oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.activations import ActivationConfig, ActivationEngine
from repro.core import catmull_rom as cr
from repro.core.fixed_point import Q2_13, dequantize, quantize
from repro.configs import registry
from repro.data import DataConfig, SyntheticPipeline
from repro.kernels import ops, ref
from repro.launch import steps as steps_mod
from repro.models import model as M
from repro.optim import adamw


def main():
    # -- 1. the spline engine vs exact tanh ------------------------------
    print("=" * 70)
    print("1. Catmull-Rom spline tanh (paper flagship: depth 32, range ±4)")
    x = jnp.linspace(-5, 5, 11)
    eng_cr = ActivationEngine(ActivationConfig(impl="cr", depth=32))
    eng_pwl = ActivationEngine(ActivationConfig(impl="pwl", depth=32))
    exact = np.tanh(np.asarray(x))
    print(f"{'x':>8} {'exact':>10} {'CR':>10} {'PWL':>10}")
    for xi, e, c, p in zip(x, exact, eng_cr.tanh(x), eng_pwl.tanh(x)):
        print(f"{float(xi):8.2f} {e:10.6f} {float(c):10.6f} {float(p):10.6f}")
    grid = jnp.linspace(-4, 4, 100001)
    err_cr = jnp.max(jnp.abs(eng_cr.tanh(grid) - jnp.tanh(grid)))
    err_pwl = jnp.max(jnp.abs(eng_pwl.tanh(grid) - jnp.tanh(grid)))
    print(f"max |err| on (-4,4): CR {float(err_cr):.2e}  PWL "
          f"{float(err_pwl):.2e}  (paper: 1.52e-4 vs 1.58e-3)")

    # -- 2. bit-accurate Q2.13 datapath ----------------------------------
    print("\n" + "=" * 70)
    print("2. Bit-accurate Q2.13 datapath (paper Fig. 3: 16-bit in/out)")
    ftab = cr.build_fixed_table(np.tanh, 4.0, 32)
    xq = quantize(jnp.asarray([-2.0, -0.5, 0.3, 1.7, 3.9]), Q2_13)
    yq = cr.interpolate_fixed(ftab, xq)
    print("x (Q2.13 ints):  ", np.asarray(xq))
    print("tanh (Q2.13 ints):", np.asarray(yq))
    print("dequantized:      ", np.asarray(dequantize(yq, Q2_13)))
    print("exact:            ", np.tanh([-2.0, -0.5, 0.3, 1.7, 3.9]).round(6))

    # -- 3. the engine inside a real model -------------------------------
    print("\n" + "=" * 70)
    print("3. One train step of a small LLaMA-family model, all "
          "nonlinearities through the CR engine")
    cfg = registry.get("qwen3-0.6b", smoke=True)   # cr-d32 engine by default
    params, _ = M.materialize_params(cfg, seed=0)
    opt_state = adamw.init_state(params)
    pipe = SyntheticPipeline(cfg, DataConfig(seed=1, vocab_size=cfg.vocab_size),
                             global_batch=4, seq_len=32)
    step = jax.jit(steps_mod.make_train_step(
        cfg, steps_mod.TrainHyper(remat="none")))
    params, opt_state, metrics = step(params, opt_state, pipe(0), jnp.int32(0))
    print(f"arch={cfg.name} activation={cfg.activation.tag()} "
          f"loss={float(metrics['loss']):.4f} "
          f"gnorm={float(metrics['gnorm']):.3f}")

    # -- 4. the Pallas kernel --------------------------------------------
    print("\n" + "=" * 70)
    print("4. Pallas TPU kernel (interpret mode on CPU), vs jnp oracle")
    xs = jax.random.normal(jax.random.key(0), (64, 256)) * 2
    y_kernel = ops.cr_act(xs)
    y_oracle = ref.cr_act_ref(xs, eng_cr and cr.build_table(np.tanh, 4.0, 32))
    print(f"max |kernel - oracle| = "
          f"{float(jnp.max(jnp.abs(y_kernel - y_oracle))):.2e}")
    print("\nquickstart OK")


if __name__ == "__main__":
    main()
