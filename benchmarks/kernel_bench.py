"""Pallas-kernel micro-benchmarks (CPU interpret mode = correctness +
rough cost structure; the roofline numbers for TPU come from the dry-run).

For each kernel: wall-time vs the pure-jnp oracle at a few shapes, plus
the analytic VMEM working-set check for the chosen BlockSpecs. Interpret
mode is orders of magnitude slower than compiled TPU — the timing column
says nothing about the chip.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.activations import ActivationConfig, ActivationEngine, tanh_table
from repro.kernels import epilogue as epi
from repro.kernels import ops, ref
from repro.kernels import cr_act as cr_act_mod


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def vmem_working_set(block_rows: int, block_cols: int) -> int:
    """VMEM bytes resident per cr_act block: the f32 x and y blocks plus
    the four selected window columns (the table itself sits in SMEM)."""
    return (2 + 4) * block_rows * block_cols * 4


def run(verbose: bool = True) -> dict:
    table = tanh_table(4.0, 32)
    rows = []
    key = jax.random.key(0)
    for shape in ((256, 512), (1024, 1024)):
        x = jax.random.normal(key, shape, jnp.float32) * 2.0
        t_ref = _time(jax.jit(lambda v: ref.cr_act_ref(v, table)), x)
        t_k = _time(ops.cr_act, x)
        err = float(jnp.max(jnp.abs(ops.cr_act(x) - ref.cr_act_ref(x, table))))
        rows.append(dict(kernel="cr_act", scheme="cr_spline", lookup="select",
                         shape=shape, t_kernel_ms=t_k * 1e3,
                         t_ref_ms=t_ref * 1e3, max_abs_err=err))
    # fused GLU (distinct keys: wg == wu would mask gate/up operand swaps)
    for (m, d, f) in ((128, 256, 512),):
        kx, kg, ku = jax.random.split(key, 3)
        xs = jax.random.normal(kx, (m, d), jnp.float32)
        wg = jax.random.normal(kg, (d, f), jnp.float32) / np.sqrt(d)
        wu = jax.random.normal(ku, (d, f), jnp.float32) / np.sqrt(d)
        t_ref = _time(jax.jit(
            lambda a, b, c: ref.fused_glu_ref(a, b, c, table)), xs, wg, wu)
        t_k = _time(lambda a, b, c: ops.fused_glu(a, b, c), xs, wg, wu)
        err = float(jnp.max(jnp.abs(
            ops.fused_glu(xs, wg, wu) - ref.fused_glu_ref(xs, wg, wu, table))))
        rows.append(dict(kernel="fused_glu", scheme="cr_spline", lookup="-",
                         shape=(m, d, f),
                         t_kernel_ms=t_k * 1e3, t_ref_ms=t_ref * 1e3,
                         max_abs_err=err))

    # every spline epilogue through the single-pass element-wise kernel
    x_epi = jax.random.normal(key, (256, 512), jnp.float32) * 2.0
    for act in epi.EPILOGUES:
        etab = epi.table_for(act, 4.0, 32)
        t_ref = _time(jax.jit(lambda v, a=act, tb=etab: ref.act_ref(v, a, tb)),
                      x_epi)
        t_k = _time(lambda v, a=act: ops.act(v, a), x_epi)
        err = float(jnp.max(jnp.abs(
            ops.act(x_epi, act) - ref.act_ref(x_epi, act, etab))))
        rows.append(dict(kernel="epilogue", scheme="cr_spline", lookup=act,
                         shape=(256, 512),
                         t_kernel_ms=t_k * 1e3, t_ref_ms=t_ref * 1e3,
                         max_abs_err=err))

    # the tanh kernel under every other registered approximant scheme
    # (scheme column segments cross-PR perf trajectories per approximant;
    # reference = the scheme's own jnp block, so max|err| isolates the
    # kernel lowering, not the approximation quality)
    from repro.core import approximant as apx
    for scheme in apx.schemes():
        if scheme == "cr_spline":
            continue                      # covered by the rows above
        spec = apx.spec_for(scheme, "tanh", depth=32, degree=5)
        params = jnp.asarray(apx.params_for(spec, "tanh"))
        t_ref = _time(jax.jit(
            lambda v, s=spec, p=params: apx.block(v, p, s)), x_epi)
        t_k = _time(lambda v, s=scheme: ops.act(v, "tanh", method=s,
                                                depth=32, degree=5), x_epi)
        err = float(jnp.max(jnp.abs(
            ops.act(x_epi, "tanh", method=scheme, depth=32, degree=5)
            - apx.block(x_epi, params, spec))))
        rows.append(dict(kernel="epilogue", scheme=scheme, lookup="tanh",
                         shape=(256, 512),
                         t_kernel_ms=t_k * 1e3, t_ref_ms=t_ref * 1e3,
                         max_abs_err=err))

    # fused vs unfused GLU MLP (the fuse_mlp hot path): one kernel launch
    # vs two einsum matmuls + an engine nonlinearity + a multiply
    eng = ActivationEngine(ActivationConfig(impl="cr", depth=32))
    mlp_rows = []
    for (m, d, f) in ((64, 256, 512),):
        kx, kg, ku = jax.random.split(jax.random.fold_in(key, 1), 3)
        xs = jax.random.normal(kx, (m, d), jnp.float32) * 0.5
        wg = jax.random.normal(kg, (d, f), jnp.float32) / np.sqrt(d)
        wu = jax.random.normal(ku, (d, f), jnp.float32) / np.sqrt(d)

        def unfused(a, b, c):
            return eng.silu(a @ b) * (a @ c)

        t_unfused = _time(jax.jit(unfused), xs, wg, wu)
        t_fused = _time(lambda a, b, c: ops.fused_glu(a, b, c, act="silu"),
                        xs, wg, wu)
        err = float(jnp.max(jnp.abs(
            ops.fused_glu(xs, wg, wu, act="silu") - unfused(xs, wg, wu))))
        mlp_rows.append(dict(kernel="mlp_fused_vs_unfused",
                             scheme="cr_spline",
                             shape=(m, d, f), act="silu",
                             t_fused_ms=t_fused * 1e3,
                             t_unfused_ms=t_unfused * 1e3,
                             max_abs_err=err,
                             hbm_writes_fused=1, hbm_writes_unfused=3))

    ws = vmem_working_set(cr_act_mod.DEFAULT_BLOCK_ROWS,
                          cr_act_mod.DEFAULT_BLOCK_COLS)
    checks = []
    if ws > 16 * 2 ** 20:
        checks.append(f"cr_act default block working set {ws} > 16 MiB VMEM")
    for r in rows:
        tol = 1e-5 if r["kernel"] in ("cr_act", "epilogue") else 5e-4
        if r["max_abs_err"] > tol:  # (5e-4: f32 matmul assoc)
            checks.append(f"{r['kernel']}/{r['lookup']} {r['shape']} err "
                          f"{r['max_abs_err']:.2e} > {tol}")
    for r in mlp_rows:
        if r["max_abs_err"] > 5e-4:
            checks.append(f"{r['kernel']} {r['shape']} err "
                          f"{r['max_abs_err']:.2e} > 5e-4")

    if verbose:
        print("\n== Pallas kernels (interpret mode; timings are relative) ==")
        for r in rows:
            print(f"{r['kernel']:>10}[{r['scheme']}]/{r['lookup']:<9} "
                  f"{str(r['shape']):<18}"
                  f" kernel {r['t_kernel_ms']:9.1f} ms | jnp-ref "
                  f"{r['t_ref_ms']:7.1f} ms | max|err| {r['max_abs_err']:.2e}")
        for r in mlp_rows:
            print(f"{r['kernel']:>10}/{r['act']:<9} {str(r['shape']):<18}"
                  f" fused {r['t_fused_ms']:10.1f} ms | unfused "
                  f"{r['t_unfused_ms']:7.1f} ms | max|err| "
                  f"{r['max_abs_err']:.2e} | HBM writes "
                  f"{r['hbm_writes_fused']} vs {r['hbm_writes_unfused']}")
        print(f"cr_act default block VMEM working set: {ws/2**10:.0f} KiB "
              f"(16 MiB/core budget)")
        status = "PASS" if not checks else "FAIL"
        for c in checks:
            print("  CHECK FAILED:", c)
        print(f"kernel_bench: {status}")
    return {"rows": rows, "mlp": mlp_rows, "checks": checks,
            "status": "PASS" if not checks else "FAIL"}


if __name__ == "__main__":
    # --json prints to stdout; --json PATH writes the file (CI baseline)
    as_json = "--json" in sys.argv
    json_path = None
    if as_json:
        i = sys.argv.index("--json")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            json_path = sys.argv[i + 1]
    result = run(verbose=not as_json or json_path is not None)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2, default=str)
    elif as_json:
        print(json.dumps(result, indent=2, default=str))
