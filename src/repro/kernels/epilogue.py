"""The approximant-epilogue subsystem: one in-kernel activation codepath
per registered scheme, dispatched on ``ApproxSpec.scheme``.

The paper's thesis is that a single small Catmull-Rom tanh unit serves
every nonlinearity in an accelerator — sigmoid, SiLU and GELU derive
from it by identities, softplus from a second tiny residual table. This
module is that unit for Pallas TPU kernels, generalized over the
Approximant API (``core/approximant.py``): the same epilogue wiring and
kernel builders run any registered scheme (cr_spline / pwl / poly /
rational), with the scheme's flat f32 params as a generic SMEM operand.
It owns:

  * ``TableSpec`` — now an alias of ``approximant.ApproxSpec``, the
    hashable static geometry (scheme, depth/degree, domain, symmetry,
    fixed-point format) kernels close over while the params array rides
    along whole in SMEM, read as scalars;
  * ``_cr_tanh_block`` — the paper's Fig. 2/3 datapath on a 2D f32
    block (index/t split, 4-tap basis MAC, saturation, optional
    odd-symmetry sign fixup), reading its window LUT through
    ``approximant._gather_columns`` (a select chain inside kernels, an
    XLA gather outside). This is the single authoritative CR block —
    the approximant registry's ``cr_spline`` scheme delegates here;
    non-CR blocks live with their schemes in ``core/approximant.py``;
  * the composable epilogues ``tanh | sigmoid | silu | gelu_tanh |
    softplus``, each a pure f32->f32 block function built on the
    spec's scheme block (``make_epilogue``), plus ``table_for`` /
    ``params_for`` mapping each epilogue to what it reads (the tanh
    approximant for the first four, the even softplus residual for the
    last);
  * the two kernel builders every public op instantiates:
      - ``elementwise_2d``: matmul-free epilogue — grid over (rows,
        cols) blocks, epilogue applied straight to the input block
        (``cr_act_2d`` is the ``act="tanh"`` instance);
      - ``glu_2d``: GLU epilogue — (M, N, K) matmul grid with two f32
        VMEM accumulators, epilogue fired on the gate accumulator at
        the last K step (``fused_glu_2d`` is an instance).

Both builders compile their kernel when the program is lowered for a
TPU and interpret it when it is lowered for the CPU (``_on_platform``);
any other platform fails to lower.

Downstream, ``ops.py`` wraps these with padding/jit, the
``ActivationEngine`` dispatches every ``use_kernel=True`` nonlinearity
here as a SINGLE ``pallas_call``, and ``models/layers.apply_mlp`` routes
whole GLU FFNs through ``glu_2d`` under ``ModelConfig.fuse_mlp``. Every
future variant (bf16 tables, fixed-point datapath, attention epilogues)
is a local edit to this file or a new ``@register`` scheme in
``core/approximant.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import approximant
from repro.core import catmull_rom as cr
from repro.core.approximant import ApproxSpec

SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)

EPILOGUES = ("tanh", "sigmoid", "silu", "gelu_tanh", "softplus")

DEFAULT_BLOCK_ROWS = 32
DEFAULT_BLOCK_COLS = 512

# Back-compat: the spline LUT spec is the cr_spline instance of the
# generic ApproxSpec (same fields, same ``of``; extra scheme/degree/
# symmetry/format fields default to the paper's flagship CR geometry).
TableSpec = ApproxSpec


def table_for(act: str, x_max: float, depth: int) -> cr.SplineTable:
    """The spline table an epilogue reads. tanh-family epilogues share
    ONE tanh table (the paper's single hardware unit); softplus has its
    own even residual table h(u) = log(1 + e^-u), widened exactly like
    the engine's jnp path so kernel and jnp backends agree bit-for-bit
    in table contents."""
    from repro.core.activations import softplus_residual_table, tanh_table
    if act == "softplus":
        return softplus_residual_table(max(x_max, 8.0), max(depth, 64))
    if act in EPILOGUES:
        return tanh_table(x_max, depth)
    raise ValueError(f"unknown epilogue {act!r}")


def _spec_for_epilogue(act: str, scheme: str, x_max: float, depth: int,
                       degree: int = 3) -> ApproxSpec:
    """The spec an epilogue runs under (private: the public per-scheme
    entry point is ``approximant.spec_for(scheme, act, ...)`` — this
    internal helper exists for the CR table route and deliberately is
    not a same-name twin with swapped arguments). The cr_spline route
    goes through ``table_for`` (cached SplineTables -> bit-identical CR
    specs); other schemes resolve through the approximant registry,
    with the same softplus widening everywhere."""
    if scheme == "cr_spline":
        return TableSpec.of(table_for(act, x_max, depth))
    return approximant.spec_for(scheme, act, x_max=x_max, depth=depth,
                                degree=degree)


def params_for(act: str, spec: ApproxSpec) -> np.ndarray:
    """The flat f32 params array an epilogue reads under ``spec`` (the
    scheme-generic analogue of ``table_for(...).windows``). Every scheme
    — cr_spline included — builds from the spec's own geometry and
    saturation, so a caller-supplied spec is honored in full."""
    return approximant.params_for(spec, approximant.target_of(act))


def _basis_weights_f32(t):
    """CR basis (incl. the 1/2) in f32 Horner form; t in [0, 1)."""
    w0 = 0.5 * (((-t + 2.0) * t - 1.0) * t)
    w1 = 0.5 * ((3.0 * t - 5.0) * t * t + 2.0)
    w2 = 0.5 * (((-3.0 * t + 4.0) * t + 1.0) * t)
    w3 = 0.5 * ((t - 1.0) * t * t)
    return w0, w1, w2, w3


def _cr_tanh_block(v, win, *, spec: TableSpec, lookup: str = "take",
                   odd: bool = True):
    """CR-spline interpolation of a 2D f32 block — the shared datapath.

    TPU adaptation of the paper's Fig. 2/3: index/t split is a float
    multiply + floor (hardware: bit slice), the basis polynomials run in
    Horner form on the VPU lanes, the 4-tap MAC is a lane-wise FMA chain.
    The [depth, 4] window LUT is read through
    ``approximant._gather_columns``: ``lookup="select"`` inside kernels
    (``win`` is then the SMEM ref), ``"take"`` under XLA.

    ``odd=True`` evaluates on |v| and restores the sign (tanh family);
    ``odd=False`` evaluates the table at v directly (softplus residual —
    the caller supplies a non-negative argument).
    """
    av = jnp.abs(v) if odd else v
    u = av * spec.inv_period
    k = jnp.clip(jnp.floor(u), 0.0, spec.depth - 1.0)
    t = u - k                                        # in [0, 1)
    p0, p1, p2, p3 = approximant._gather_columns(win, k.astype(jnp.int32),
                                                 lookup)
    w0, w1, w2, w3 = _basis_weights_f32(t)
    y = p0 * w0 + p1 * w1 + p2 * w2 + p3 * w3        # the 4-tap MAC
    y = jnp.where(av >= spec.x_max, jnp.float32(spec.saturation), y)
    if odd:
        y = jnp.where(v < 0.0, -y, y)                # odd-symmetry fixup
    return y


def _block_for(spec: ApproxSpec, lookup: str):
    """The scheme's array datapath ``fn(v, params, odd=...)``. cr_spline
    binds ``_cr_tanh_block`` directly (bit-identical to the pre-registry
    subsystem); other schemes dispatch through the approximant registry
    — all of them pure element-wise f32 math, legal inside kernels."""
    if spec.scheme == "cr_spline":
        return functools.partial(_cr_tanh_block, spec=spec, lookup=lookup)

    def blk(v, params, odd: bool = True):
        return approximant.block(v, params, spec, lookup=lookup, odd=odd)
    return blk


def make_epilogue(act: str, spec: TableSpec, lookup: str = "take"):
    """Build the f32-block epilogue ``fn(v, params) -> y`` for ``act``.

    All tanh-derived epilogues reuse ONE approximant evaluation per
    element — the identities below are the paper's wire-level
    derivations, and they hold for every registered scheme:
        sigmoid(x) = (1 + tanh(x/2)) / 2        (x/2 is a wire shift)
        silu(x)    = x * sigmoid(x)             (one extra multiplier)
        gelu_tanh  = x/2 * (1 + tanh(c(x + 0.044715 x^3)))
        softplus   = relu(x) + h(|x|)           (own even residual table)
    """
    block = _block_for(spec, lookup)
    if act == "tanh":
        return lambda v, win: block(v, win)
    if act == "sigmoid":
        return lambda v, win: 0.5 * (1.0 + block(v * 0.5, win))
    if act == "silu":
        return lambda v, win: v * (0.5 * (1.0 + block(v * 0.5, win)))
    if act == "gelu_tanh":
        def gelu(v, win):
            inner = SQRT_2_OVER_PI * (v + 0.044715 * v * v * v)
            return 0.5 * v * (1.0 + block(inner, win))
        return gelu
    if act == "softplus":
        return lambda v, win: jax.nn.relu(v) + block(jnp.abs(v), win,
                                                     odd=False)
    raise ValueError(f"unknown epilogue {act!r}")


def _on_platform(call, *args):
    """``call(*args, interpret=...)`` decided by the platform the program
    is lowered for, not by the process's default backend: compiled by
    Mosaic for a TPU, interpreted for the CPU, and refused (no branch)
    anywhere else. A TPU compile rehearsed from a CPU-only process
    therefore holds the real kernel."""
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(call, interpret=True),
        tpu=functools.partial(call, interpret=False))


# The params ride whole in SMEM: the kernels read them as scalars
# (``lookup="select"``), so every vector op stays a 2-D VPU op.
_PARAMS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _check_params(params, spec: ApproxSpec):
    expected = approximant.get(spec.scheme).params_shape(spec)
    assert tuple(params.shape) == tuple(expected), (params.shape, spec)


# ---------------------------------------------------------------------------
# kernel builder 1: matmul-free epilogue (element-wise over 2D blocks)
# ---------------------------------------------------------------------------

def _elementwise_kernel(x_ref, win_ref, o_ref, *, act: str, spec: TableSpec):
    epi = make_epilogue(act, spec, "select")
    x = x_ref[...].astype(jnp.float32)               # [bm, bn]
    o_ref[...] = epi(x, win_ref).astype(o_ref.dtype)


def elementwise_2d(x, params, *, spec: TableSpec, act: str = "tanh",
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   block_cols: int = DEFAULT_BLOCK_COLS):
    """Apply one approximant epilogue to a 2D array in a single
    pallas_call.

    Grid: 2D blocks over (rows, cols); block_cols a multiple of 128
    (lane width), block_rows a multiple of 8 (sublane). Dims must divide
    by the block shape — ``ops.act`` handles padding/reshaping.
    ``params`` is the scheme's flat f32 array (CR windows, PWL segment
    pairs, poly coefficients, Padé rows), whole-array resident in SMEM.
    """
    rows, cols = x.shape
    _check_params(params, spec)
    assert rows % block_rows == 0 and cols % block_cols == 0, (x.shape,)
    kernel = functools.partial(_elementwise_kernel, act=act, spec=spec)
    blk = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))

    def call(x, params, *, interpret):
        return pl.pallas_call(
            kernel,
            grid=(rows // block_rows, cols // block_cols),
            in_specs=[blk, _PARAMS_SPEC],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, params)

    return _on_platform(call, x, params)


# ---------------------------------------------------------------------------
# kernel builder 2: GLU epilogue (fused matmuls + spline on the accumulator)
# ---------------------------------------------------------------------------

def _glu_kernel(x_ref, wg_ref, wu_ref, win_ref, o_ref, gate_acc, up_acc, *,
                n_k: int, act: str, spec: TableSpec):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        gate_acc[...] = jnp.zeros_like(gate_acc)
        up_acc[...] = jnp.zeros_like(up_acc)

    x = x_ref[...]
    gate_acc[...] += jax.lax.dot(x, wg_ref[...],
                                 preferred_element_type=jnp.float32)
    up_acc[...] += jax.lax.dot(x, wu_ref[...],
                               preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _done():
        epi = make_epilogue(act, spec, "select")
        y = epi(gate_acc[...], win_ref) * up_acc[...]
        o_ref[...] = y.astype(o_ref.dtype)


def glu_2d(x, w_gate, w_up, params, *, spec: TableSpec, act: str = "silu",
           block_m: int = 128, block_n: int = 128, block_k: int = 512):
    """out[M,N] = epilogue(x[M,K] @ w_gate[K,N]) * (x @ w_up) — the TPU
    embodiment of the paper's deployment: the activation unit reads the
    MAC-array accumulator directly, so the gate projection never
    round-trips to HBM.

    Grid: (M/bm, N/bn, K/bk), K innermost (TPU minor grid dim) so the
    two f32 VMEM scratch accumulators live across the K loop; the
    epilogue fires at the final K step. Dims must divide by the block
    shape (``ops.fused_glu`` pads).
    """
    m, k = x.shape
    k2, n = w_gate.shape
    assert k == k2 and w_up.shape == (k, n)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        x.shape, w_gate.shape)
    _check_params(params, spec)
    n_k = k // block_k
    kernel = functools.partial(_glu_kernel, n_k=n_k, act=act, spec=spec)
    w_blk = pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j))

    def call(x, w_gate, w_up, params, *, interpret):
        return pl.pallas_call(
            kernel,
            grid=(m // block_m, n // block_n, n_k),
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
                w_blk, w_blk, _PARAMS_SPEC,
            ],
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_m, block_n), jnp.float32),
                pltpu.VMEM((block_m, block_n), jnp.float32),
            ],
            interpret=interpret,
        )(x, w_gate, w_up, params)

    return _on_platform(call, x, w_gate, w_up, params)
