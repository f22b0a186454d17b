"""Cubic Catmull-Rom spline interpolation (paper Eq. 2/3).

The CR spline interpolates uniformly-sampled control points P_{k-1..k+2}
with basis polynomials of the local parameter t in [0, 1):

    f = 1/2 * [P_{k-1} P_k P_{k+1} P_{k+2}] . [ -t^3 + 2t^2 - t
                                                 3t^3 - 5t^2 + 2
                                                -3t^3 + 4t^2 + t
                                                 t^3 -  t^2      ]

All basis coefficients are integers (after the global 1/2), which is the
paper's key hardware property: no coefficient ROM, just shifts and adds.

This module supplies:
  * the basis matrix and basis evaluation (float and Q-format fixed point),
  * knot-table construction for an arbitrary scalar function,
  * a vectorized float interpolator (pure jnp; the oracle for kernels),
  * a bit-accurate fixed-point interpolator emulating the Fig. 3 datapath.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .fixed_point import LimbStack, QFormat, Q2_13, fx_dot4, quantize

# Rows act on [P_{k-1}, P_k, P_{k+1}, P_{k+2}]; columns are t^3, t^2, t, 1.
# f(t) = 0.5 * P . (BASIS @ [t^3, t^2, t, 1])
BASIS = np.array(
    [
        [-1.0, 2.0, -1.0, 0.0],
        [3.0, -5.0, 0.0, 2.0],
        [-3.0, 4.0, 1.0, 0.0],
        [1.0, -1.0, 0.0, 0.0],
    ]
)


def basis_weights(t):
    """The four CR basis polynomial values at t (float), incl. the 1/2.

    Uses Horner form; returns shape t.shape + (4,).
    """
    t = jnp.asarray(t)
    w0 = 0.5 * (((-t + 2.0) * t - 1.0) * t)          # -t^3 + 2t^2 - t
    w1 = 0.5 * ((3.0 * t - 5.0) * t * t + 2.0)       # 3t^3 - 5t^2 + 2
    w2 = 0.5 * (((-3.0 * t + 4.0) * t + 1.0) * t)    # -3t^3 + 4t^2 + t
    w3 = 0.5 * ((t - 1.0) * t * t)                   # t^3 - t^2
    return jnp.stack([w0, w1, w2, w3], axis=-1)


class SplineTable(NamedTuple):
    """Uniform CR knot table for a scalar function on [0, x_max).

    ``values`` holds f at knots -1 .. depth+2 (one extra on the left, two
    on the right) so that every interior segment has its full 4-point
    window — this mirrors the hardware's implicit boundary handling.
    ``windows`` is the precomputed [depth, 4] per-segment control-point
    window (what the paper stores as the LUT + neighbor wiring).
    """

    x_max: float
    depth: int            # number of segments in [0, x_max)
    period: float         # x_max / depth (the paper's "sampling period")
    values: np.ndarray    # [depth + 4] knot values, f((k-1)*period), k=0..depth+3
    windows: np.ndarray   # [depth, 4] -> values[k-1 : k+3] for segment k
    saturation: float     # f(x) for x >= x_max (odd-extended for x <= -x_max)


def build_table(fn: Callable[[np.ndarray], np.ndarray], x_max: float, depth: int,
                saturation: float | None = None) -> SplineTable:
    """Build a CR knot table for ``fn`` sampled uniformly on [0, x_max].

    ``fn`` must accept numpy float64. Knots outside the range (k = -1 and
    k = depth+1, depth+2) are computed exactly from ``fn`` — the hardware
    equivalent is two extra wired constants.
    """
    period = x_max / depth
    ks = np.arange(-1, depth + 3, dtype=np.float64)  # -1 .. depth+2
    values = fn(ks * period).astype(np.float64)
    if saturation is None:
        saturation = float(fn(np.asarray([x_max], dtype=np.float64))[0])
    idx = np.arange(depth)[:, None] + np.arange(4)[None, :]  # values[k-1+1 .. k+2+1]
    windows = values[idx]
    return SplineTable(float(x_max), int(depth), float(period), values, windows, float(saturation))


def interpolate(table: SplineTable, x, odd: bool = True):
    """Float CR interpolation of the tabled function at x (pure jnp oracle).

    ``odd=True`` applies the paper's odd-symmetry trick: evaluate on |x|
    and restore the sign. Out-of-range |x| >= x_max saturates. The ops
    carry the ``cr_spline`` scope, by which a device trace finds them.
    """
    with jax.named_scope("cr_spline"):
        x = jnp.asarray(x)
        ax = jnp.abs(x) if odd else x
        u = ax / table.period
        k = jnp.clip(jnp.floor(u), 0, table.depth - 1).astype(jnp.int32)
        t = u - k.astype(u.dtype)                      # in [0,1)
        w = basis_weights(t)                           # [..., 4]
        windows = jnp.asarray(table.windows, dtype=x.dtype)  # [depth, 4]
        p = windows[k]                                 # [..., 4]
        y = jnp.sum(p * w, axis=-1)
        y = jnp.where(ax >= table.x_max,
                      jnp.asarray(table.saturation, y.dtype), y)
        if odd:
            y = jnp.where(x < 0, -y, y)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Bit-accurate fixed-point datapath (paper Fig. 3)
# ---------------------------------------------------------------------------

class FixedTable(NamedTuple):
    """Quantized knot windows + index geometry for the Fig. 3 datapath.

    For the paper's flagship config (x_max=4, depth=32, Q2.13): the input's
    top 5 magnitude bits (above the 8 LSBs) index the LUT and the low
    ``t_bits`` = 8 bits are t. We generalize: depth must be a power of two
    and period a power of two over x_max so that index/t split is a pure
    bit slice, exactly as in hardware.
    """

    fmt: QFormat
    x_max: float
    depth: int
    t_bits: int           # number of low bits forming t
    windows_q: np.ndarray  # [depth, 4] int32 control points (Q fmt)
    sat_q: int            # saturated output value (Q fmt)


def build_fixed_table(fn, x_max: float, depth: int, fmt: QFormat = Q2_13) -> FixedTable:
    table = build_table(fn, x_max, depth)
    # bits of the magnitude representing one period: period * scale = 2^t_bits
    t_scaled = table.period * fmt.scale
    t_bits = int(round(np.log2(t_scaled)))
    if 2 ** t_bits != int(round(t_scaled)):
        raise ValueError(
            f"period {table.period} is not a power-of-two number of LSBs in {fmt}"
        )
    windows_q = np.asarray(quantize(table.windows, fmt))
    sat_q = int(np.asarray(quantize(np.float64(table.saturation), fmt)))
    return FixedTable(fmt, float(x_max), int(depth), t_bits, windows_q, sat_q)


def _wrap_i32(v: int) -> int:
    """Python int -> its int32 two's-complement representative."""
    v &= 0xFFFFFFFF
    return v - (1 << 32) if v >= (1 << 31) else v


def basis_weights_fixed(t_q, ftab: FixedTable):
    """Fixed-point basis evaluation: t_q is the raw low-bit residue
    (0 .. 2^t_bits - 1).

    Key hardware observation (this is what lets the paper's circuit hit
    its Table I/II numbers): t has only ``t_bits`` (= 10 for the
    flagship config) significant fractional bits, so t^2 (2*tb bits) and
    t^3 (3*tb bits) are EXACTLY representable with small multipliers.
    The four basis polynomials have integer coefficients, so the whole
    t-vector is computed exactly, aligned at 3*t_bits fractional bits;
    the only rounding in the datapath is the single shift-round at the
    MAC output. (An earlier variant of this datapath rounded every
    Horner step back to Q2.13 and measurably lost one LSB of max error —
    0.000276 vs the paper's 0.000152; recorded in EXPERIMENTS.md.)

    Returns int32 [..., 4], scaled 2^(3*t_bits+1) x the true basis value
    (the +1 carries the CR global 1/2, folded into the MAC's final
    shift) — EXACT MOD 2^32. Two's-complement wraparound of the Horner
    intermediates is harmless because every true basis value fits 32
    bits, with ONE exception: w1(t=0) = 2^(3tb+1) = 2^31 for tb=10,
    which wraps to -2^31. t = 0 is a knot hit, so ``interpolate_fixed``
    bypasses the MAC there (the hardware equivalent is the index-hit
    mux). int64 is not an option for the lattice: it neither exists on
    TPU vector lanes nor lowers reliably inside remat'd scans on CPU
    (jax re-lowers jax.checkpoint constants under the ambient 32-bit
    config, emitting invalid mixed i64/i32 ops).

    Wide geometries (t_bits > 10: depth 8/16 at Q2.13, depth <= 64 at
    Q2.16) exceed 32 lattice bits, so the basis comes back as a
    ``LimbStack`` of radix-2^s int32 limbs computed exactly with limb
    arithmetic (``_wide_basis_limbs``); fx_dot4 dots the limbs
    separately. Every depth is int32-only and jit/TPU-legal.
    """
    tb = ftab.t_bits
    if 3 * tb + 1 > 31:
        return _wide_basis_limbs(t_q, tb)
    T = t_q.astype(jnp.int32)                 # t * 2^tb, exact
    T2 = T * T                                # t^2 * 2^2tb, exact
    T3 = T2 * T                               # t^3 * 2^3tb, exact
    two_pow = _wrap_i32(2 << (3 * tb))        # 2^(3tb+1) mod 2^32
    # align everything at 3*tb fractional bits; all coefficients integer.
    w0 = -T3 + 2 * (T2 << tb) - (T << (2 * tb))
    w1 = 3 * T3 - 5 * (T2 << tb) + two_pow
    w2 = -3 * T3 + 4 * (T2 << tb) + (T << (2 * tb))
    w3 = T3 - (T2 << tb)
    return jnp.stack([w0, w1, w2, w3], axis=-1)


# Limb width for wide basis lattices. 10 bits keeps every partial dot in
# fx_dot4 exact on int32 lanes for formats up to Q2.18 (int+frac+s+2 <= 31)
# and every limb product here below 2^(s + t_bits) <= 2^25.
WIDE_LIMB_BITS = 10


def _wide_basis_limbs(t_q, tb: int, s: int = WIDE_LIMB_BITS) -> LimbStack:
    """Exact CR basis on a lattice wider than 31 bits, as radix-2^s limbs.

    The four basis values are integer combinations of T^3, T^2*2^tb,
    T*2^2tb and the constant 2^(3tb+1), all aligned at 3*tb fractional
    bits (times the folded CR 1/2). T < 2^tb with tb <= 15, so T^2 is
    int32-exact but T^3 (up to 3*tb = 45 bits) is not: T^3 is formed by
    limb-splitting T^2 and multiplying each limb by T (products below
    2^(s+tb) <= 2^25), and the shifted terms land piece-aligned via
    divmod(shift, s). Per-limb accumulators stay far below 2^31 (integer
    coefficients <= 5 on pieces < 2^25), and one signed carry-normalize
    pass produces canonical limbs: 0..m-2 in [0, 2^s), top signed.
    Everything is exact integer arithmetic — no wraparound, no int64.
    """
    S = 3 * tb + 1                    # total lattice shift (incl. CR 1/2)
    m = -(-(S + 1) // s)              # limbs covering S+1 magnitude bits
    mask = (1 << s) - 1
    T = t_q.astype(jnp.int32)         # t * 2^tb, exact
    T2 = T * T                        # t^2 * 2^2tb, exact (2*tb <= 30)
    t2 = [(T2 >> (k * s)) & mask for k in range(-(-2 * tb // s))]
    t1 = [(T >> (k * s)) & mask for k in range(-(-tb // s))]
    zero = jnp.zeros_like(T)
    q2, r2 = divmod(tb, s)            # T^2 << tb placement
    q1, r1 = divmod(2 * tb, s)        # T << 2tb placement
    qc, rc = divmod(S, s)             # constant 2^(3tb+1) placement

    def combine(c3: int, c2: int, c1: int, const: bool):
        acc = [zero] * m
        for k, piece in enumerate(t2):
            acc[k] = acc[k] + c3 * (piece * T)          # T^3 pieces
            acc[k + q2] = acc[k + q2] + c2 * (piece << r2)   # T^2 << tb
        if c1:
            for k, piece in enumerate(t1):
                acc[k + q1] = acc[k + q1] + c1 * (piece << r1)  # T << 2tb
        if const:
            acc[qc] = acc[qc] + (1 << rc)
        out, carry = [], zero
        for k in range(m - 1):
            v = acc[k] + carry
            out.append(v & mask)
            carry = v >> s            # arithmetic: exact floor
        out.append(acc[m - 1] + carry)
        return out

    ws = [combine(-1, 2, -1, False),      # w0 = -T3 + 2 T2<<tb - T<<2tb
          combine(3, -5, 0, True),        # w1 = 3 T3 - 5 T2<<tb + 2^(3tb+1)
          combine(-3, 4, 1, False),       # w2 = -3 T3 + 4 T2<<tb + T<<2tb
          combine(1, -1, 0, False)]       # w3 = T3 - T2<<tb
    limbs = tuple(jnp.stack([w[k] for w in ws], axis=-1) for k in range(m))
    return LimbStack(s, limbs)


def interpolate_fixed(ftab: FixedTable, x_q):
    """Bit-accurate CR interpolation on the integer lattice.

    ``x_q``: int32 Q-format input (e.g. from ``quantize``). Returns int32
    Q-format output. Mirrors Fig. 3: |x| -> (msbs -> LUT window, lsbs -> t),
    4-tap MAC, sign fixup, saturation for |x| >= x_max.
    """
    fmt = ftab.fmt
    x_q = jnp.asarray(x_q, jnp.int32)
    sign_neg = x_q < 0
    mag = jnp.abs(x_q)
    idx = (mag >> ftab.t_bits).astype(jnp.int32)
    in_range = idx < ftab.depth
    idx_c = jnp.clip(idx, 0, ftab.depth - 1)
    t_q = mag & ((1 << ftab.t_bits) - 1)
    w = basis_weights_fixed(t_q, ftab)       # [..., 4], frac = 3*t_bits (+CR 1/2)
    p = jnp.asarray(ftab.windows_q)[idx_c]                  # [..., 4]
    # wide MAC: products at frac_bits + 3*t_bits fraction; ONE final
    # shift-round back to the output format (+1 folds the CR global 1/2).
    y = fx_dot4(p, w, fmt, extra_shift=3 * ftab.t_bits - fmt.frac_bits + 1)
    # t = 0 is an exact knot hit whose basis weight 2^(3tb+1) wraps the
    # 32-bit lattice (see basis_weights_fixed): bypass with the knot
    # value, which IS the exact MAC result there (hardware: index mux).
    y = jnp.where(t_q == 0, p[..., 1], y)
    y = jnp.where(in_range, y, jnp.int32(ftab.sat_q))
    return jnp.where(sign_neg, -y, y)


# ---------------------------------------------------------------------------
# PWL baseline (paper Tables I/II comparison)
# ---------------------------------------------------------------------------

def interpolate_pwl(table: SplineTable, x, odd: bool = True):
    """Piecewise-linear interpolation over the same knots (paper baseline)."""
    x = jnp.asarray(x)
    ax = jnp.abs(x) if odd else x
    u = ax / table.period
    k = jnp.clip(jnp.floor(u), 0, table.depth - 1).astype(jnp.int32)
    t = u - k.astype(u.dtype)
    knots = jnp.asarray(table.values, dtype=x.dtype)
    y0 = knots[k + 1]      # values is offset by one (k=-1 stored at 0)
    y1 = knots[k + 2]
    y = y0 + t * (y1 - y0)
    y = jnp.where(ax >= table.x_max, jnp.asarray(table.saturation, y.dtype), y)
    if odd:
        y = jnp.where(x < 0, -y, y)
    return y.astype(x.dtype)
