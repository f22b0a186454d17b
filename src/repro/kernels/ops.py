"""jit'd public wrappers around the Pallas epilogue kernels.

Handles: arbitrary leading dims (flattened to rows), padding to block
multiples, dtype pass-through and approximant-scheme selection per
epilogue. Whether a kernel is compiled (TPU) or interpreted (CPU) is
decided by the platform the program is lowered for, inside the kernel
builders (``epilogue._on_platform``).

Public surface:
  act(x, name, method=...)  one-pallas_call element-wise epilogue (any
                      of ``epilogue.EPILOGUES``) under any registered
                      approximant scheme — what the ActivationEngine
                      dispatches to under ``use_kernel=True``. The
                      default ``method`` is the paper's CR spline.
  cr_act(x)           the CR ``tanh`` instance (back-compat name)
  fused_glu(x, wg, wu, method=...) GLU matmuls fused with any epilogue
                      under any scheme

Autodiff: Pallas forward kernels are wrapped in ``jax.custom_vjp`` whose
backward recomputes the same math as pure jnp (scheme blocks are plain
traceable functions — one codepath, two lowerings). This is the flash-
attention trade: no residuals from inside the kernel, a cheap recompute
in the backward pass — which is what makes ``fuse_mlp`` trainable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import catmull_rom as cr
from repro.core.activations import tanh_table

from . import epilogue as epi

EPILOGUES = epi.EPILOGUES


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _resolve_spec_params(act: str, table: cr.SplineTable | None,
                         method: str | None, spec, depth: int, degree: int,
                         x_max: float):
    """(spec, params) for one epilogue call. The CR route (explicit
    table, ``method`` unset or a CR alias) is byte-identical to the
    pre-registry subsystem: spec from the SplineTable, params = its
    [depth, 4] windows. Other schemes resolve through the approximant
    registry."""
    if spec is not None:
        if table is not None or method is not None:
            raise ValueError(
                "spec= fully determines the approximant; don't also pass "
                f"table/method (got method={method!r})")
        return spec, jnp.asarray(epi.params_for(act, spec), jnp.float32)
    if method in (None, "cr", "cr_spline"):
        table = table or epi.table_for(act, x_max, depth)
        return (epi.TableSpec.of(table),
                jnp.asarray(table.windows, jnp.float32))
    if table is not None:
        raise ValueError(
            f"pass either a SplineTable (CR route) or method={method!r}, "
            "not both")
    spec = epi._spec_for_epilogue(act, method, x_max, depth, degree)
    return spec, jnp.asarray(epi.params_for(act, spec), jnp.float32)


# ---------------------------------------------------------------------------
# element-wise epilogues
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "act", "block_rows",
                                             "block_cols"))
def _act_impl(x, windows, *, spec, act, block_rows, block_cols):
    orig_shape = x.shape
    cols = orig_shape[-1] if orig_shape else 1   # 0-d: single element
    rows = int(np.prod(orig_shape[:-1])) if len(orig_shape) > 1 else 1
    x2 = x.reshape(rows, cols)
    # pick blocks no larger than the (padded) array
    br = min(block_rows, _pad_to(rows, 8))
    bc = min(block_cols, _pad_to(cols, 128))
    pr, pc = _pad_to(rows, br), _pad_to(cols, bc)
    if (pr, pc) != (rows, cols):
        x2 = jnp.pad(x2, ((0, pr - rows), (0, pc - cols)))
    y = epi.elementwise_2d(x2, windows, spec=spec, act=act,
                           block_rows=br, block_cols=bc)
    return y[:rows, :cols].reshape(orig_shape)


def _act_ref_math(static, x, windows):
    """jnp recompute of the epilogue for the backward pass. Its ``take``
    lookup picks the same table entries as the kernels' select chain."""
    spec, act_name = static[0], static[1]
    fn = epi.make_epilogue(act_name, spec, "take")
    return fn(x.astype(jnp.float32), windows).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _act_core(static, x, windows):
    spec, act_name, br, bc = static
    return _act_impl(x, windows, spec=spec, act=act_name, block_rows=br,
                     block_cols=bc)


def _act_core_fwd(static, x, windows):
    return _act_core(static, x, windows), (x, windows)


def _act_core_bwd(static, res, g):
    x, windows = res
    _, vjp = jax.vjp(functools.partial(_act_ref_math, static), x, windows)
    return vjp(g)


_act_core.defvjp(_act_core_fwd, _act_core_bwd)


def act(x, name: str = "tanh", table: cr.SplineTable | None = None, *,
        method: str | None = None, spec: epi.ApproxSpec | None = None,
        params=None, depth: int = 32, degree: int = 3, x_max: float = 4.0,
        block_rows: int = epi.DEFAULT_BLOCK_ROWS,
        block_cols: int = epi.DEFAULT_BLOCK_COLS):
    """Any approximant epilogue as a SINGLE Pallas kernel launch.

    Scheme selection, most specific wins: ``spec`` (a full ApproxSpec),
    a CR ``table`` (back-compat route, byte-identical to the pre-
    registry kernels), or ``method`` (a registered scheme name, with
    ``depth``/``degree``/``x_max`` as its geometry). The default is the
    paper's flagship CR table (x_max=4, depth=32; softplus widens per
    ``epilogue.table_for``). ``params`` overrides the registry-built
    parameter array with a traced one (the trainable model leaf) —
    same shape, same spec, and it rides into the kernel as the normal
    SMEM operand, so gradients flow through the custom-VJP recompute."""
    spec, p = _resolve_spec_params(name, table, method, spec, depth,
                                   degree, x_max)
    if params is not None:
        p = jnp.asarray(params, jnp.float32)
    static = (spec, name, block_rows, block_cols)
    return _act_core(static, x, p)


def cr_act(x, table: cr.SplineTable | None = None, *,
           block_rows: int = epi.DEFAULT_BLOCK_ROWS,
           block_cols: int = epi.DEFAULT_BLOCK_COLS):
    """CR-spline tanh via the Pallas kernel. ``table`` defaults to the
    paper's flagship (x_max=4, depth=32)."""
    return act(x, "tanh", table or tanh_table(4.0, 32),
               block_rows=block_rows, block_cols=block_cols)


# ---------------------------------------------------------------------------
# fused GLU
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "act", "block_m",
                                             "block_n", "block_k"))
def _fused_glu_impl(x, w_gate, w_up, windows, *, spec, act, block_m, block_n,
                    block_k):
    orig_shape = x.shape
    k = orig_shape[-1]
    m = int(np.prod(orig_shape[:-1])) if len(orig_shape) > 1 else 1
    n = w_gate.shape[-1]
    x2 = x.reshape(m, k)
    bm = min(block_m, _pad_to(m, 8))
    bn = min(block_n, _pad_to(n, 128))
    bk = min(block_k, _pad_to(k, 128))
    pm, pn, pk = _pad_to(m, bm), _pad_to(n, bn), _pad_to(k, bk)
    if (pm, pk) != (m, k):
        x2 = jnp.pad(x2, ((0, pm - m), (0, pk - k)))
    wg, wu = w_gate, w_up
    if (pk, pn) != (k, n):
        wg = jnp.pad(wg, ((0, pk - k), (0, pn - n)))
        wu = jnp.pad(wu, ((0, pk - k), (0, pn - n)))
    y = epi.glu_2d(x2, wg, wu, windows, spec=spec, act=act,
                   block_m=bm, block_n=bn, block_k=bk)
    return y[:m, :n].reshape(orig_shape[:-1] + (n,))


def _fused_glu_ref_math(static, x, w_gate, w_up, windows):
    """Unfused jnp recompute for the backward pass: f32 matmuls + the
    same (traceable) epilogue the kernel applies to its accumulator."""
    spec, act_name = static[0], static[1]
    fn = epi.make_epilogue(act_name, spec, "take")
    xf = x.astype(jnp.float32)
    gate = xf @ w_gate.astype(jnp.float32)
    up = xf @ w_up.astype(jnp.float32)
    return (fn(gate, windows) * up).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_glu_core(static, x, w_gate, w_up, windows):
    spec, act_name, bm, bn, bk = static
    return _fused_glu_impl(x, w_gate, w_up, windows, spec=spec, act=act_name,
                           block_m=bm, block_n=bn, block_k=bk)


def _fused_glu_core_fwd(static, x, w_gate, w_up, windows):
    return (_fused_glu_core(static, x, w_gate, w_up, windows),
            (x, w_gate, w_up, windows))


def _fused_glu_core_bwd(static, res, g):
    x, w_gate, w_up, windows = res
    _, vjp = jax.vjp(functools.partial(_fused_glu_ref_math, static),
                     x, w_gate, w_up, windows)
    return vjp(g)


_fused_glu_core.defvjp(_fused_glu_core_fwd, _fused_glu_core_bwd)


def fused_glu(x, w_gate, w_up, table: cr.SplineTable | None = None, *,
              act: str = "silu", method: str | None = None,
              spec: epi.ApproxSpec | None = None, params=None,
              depth: int = 32, degree: int = 3, x_max: float = 4.0,
              block_m: int = 128, block_n: int = 128, block_k: int = 512):
    """epilogue(x @ w_gate) * (x @ w_up) in one fused Pallas kernel,
    under any registered approximant scheme (selection as in ``act``;
    ``params`` overrides the built parameter array with the trainable
    model leaf, as in ``act``)."""
    spec, p = _resolve_spec_params(act, table, method, spec, depth,
                                   degree, x_max)
    if params is not None:
        p = jnp.asarray(params, jnp.float32)
    static = (spec, act, block_m, block_n, block_k)
    return _fused_glu_core(static, x, w_gate, w_up, p)
