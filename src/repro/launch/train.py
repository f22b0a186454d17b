"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run0

Wires together: config registry -> mesh + logical-axis shardings ->
synthetic data pipeline -> jitted fault-guarded train step -> TrainDriver
(checkpoint/restart, NaN rollback, straggler watchdog). Re-running the
same command resumes from the latest committed checkpoint.

On a real pod this script is the per-host main(); jax.distributed would
be initialized first and `mesh` built over all devices. Everything below
the mesh line is identical.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax

from repro.configs import registry
from repro.data import DataConfig, SyntheticPipeline
from repro.ft import FTConfig, TrainDriver
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.optim import adamw, compress
from repro.parallel import partition as part


def build_parser():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="olmo-1b",
                   help="registry id (see repro.configs.registry)")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config of the same family (CPU-friendly)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8, help="global batch")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--activation", default=None,
                   help="override activation impl: exact|cr|cr_fixed|pwl|...")
    p.add_argument("--act-impl", default=None,
                   help="approximant scheme override (cr_spline|pwl|poly|"
                        "rational|...) — validated at step build; "
                        "--act-impl-kernel routes it through the Pallas "
                        "epilogue kernels")
    p.add_argument("--act-impl-kernel", action="store_true",
                   help="with --act-impl: use_kernel=True (one pallas_call "
                        "per nonlinearity)")
    p.add_argument("--act-layers", default=None,
                   help="comma-separated per-layer approximant assignment "
                        "(one tag or impl per layer, e.g. "
                        "'pwl-d16,cr-d32'); mutually exclusive with "
                        "--act-impl")
    p.add_argument("--train-act", action="store_true",
                   help="unfreeze the approximant params (knots / "
                        "coefficients) — quantization-aware fine-tuning "
                        "when combined with a *_fixed impl")
    p.add_argument("--remat", default="none", choices=["none", "block", "dots"])
    p.add_argument("--grad-compression", action="store_true")
    p.add_argument("--data-parallel", type=int, default=0,
                   help="mesh data axis size (0 = all devices)")
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-out", default=None,
                   help="write final metrics JSON here")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()
    cfg = registry.get(args.arch, smoke=args.smoke)
    if args.activation:
        cfg = dataclasses.replace(
            cfg, activation=dataclasses.replace(cfg.activation,
                                                impl=args.activation))
    if args.act_impl_kernel and not args.act_impl:
        raise SystemExit("--act-impl-kernel requires --act-impl <scheme>")
    if args.act_impl:
        from repro.configs.common import act_impl_of
        cfg = act_impl_of(cfg, args.act_impl,
                          use_kernel=True if args.act_impl_kernel else None)
    if args.act_layers:
        from repro.configs.common import act_layers_of
        cfg = act_layers_of(cfg, args.act_layers.split(","))
    n_dev = len(jax.devices())
    dp = args.data_parallel or max(1, n_dev // args.model_parallel)
    mesh = make_host_mesh(dp, args.model_parallel)
    print(f"[train] arch={cfg.name} act={cfg.activation.tag()} "
          f"mesh={dict(mesh.shape)} devices={n_dev}")

    hyper = steps_mod.TrainHyper(
        opt=adamw.AdamWConfig(lr_peak=args.lr, warmup_steps=args.warmup,
                              decay_steps=max(args.steps, 2 * args.warmup)),
        remat=args.remat, grad_compression=args.grad_compression,
        train_act=args.train_act)

    with part.axis_rules(mesh):
        params, paxes = M.materialize_params(cfg, seed=args.seed)
        pshapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        pshard = steps_mod.axes_shardings(paxes, pshapes, mesh,
                                          part.DEFAULT_RULES)
        params = jax.tree.map(jax.device_put, params, pshard)
        opt_state = adamw.init_state(params)
        if hyper.grad_compression:
            opt_state["error"] = compress.init_error(params)

        pipe = SyntheticPipeline(
            cfg, DataConfig(seed=args.seed + 1,
                            vocab_size=min(cfg.vocab_size, 4096)),
            args.batch, args.seq)

        step_fn = jax.jit(steps_mod.make_train_step(cfg, hyper),
                          donate_argnums=(0, 1))

        ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      log_every=args.log_every)
        drv = TrainDriver.resume(step_fn, pipe, params, opt_state, ft,
                                 metadata={"arch": cfg.name,
                                           "activation": cfg.activation.tag()})
        t0 = time.time()
        remaining = max(0, args.steps - drv.step)
        drv.run(remaining)
        wall = time.time() - t0
        drv.save()

    losses = drv.losses()
    tokens = remaining * args.batch * args.seq
    summary = {
        "arch": cfg.name,
        "activation": cfg.activation.tag(),
        "steps": int(drv.step),
        "loss_first": float(losses[0]) if len(losses) else None,
        "loss_last_avg8": float(losses[-8:].mean()) if len(losses) else None,
        "wall_s": round(wall, 2),
        "tokens_per_s": round(tokens / wall, 1) if wall > 0 else None,
        "stragglers": int(sum(r.straggler for r in drv.history)),
        "skipped": int(sum(r.skipped for r in drv.history)),
    }
    print("[train] done:", json.dumps(summary, indent=1))
    if args.metrics_out:
        Path(args.metrics_out).write_text(json.dumps(summary, indent=1))
    return summary


if __name__ == "__main__":
    main()
