"""The Approximant API: one interface for every activation datapath.

The paper's CR-spline tanh is a single point in a larger hardware design
space — the same author's *Comparative Analysis of Polynomial and
Rational Approximations of Tanh for VLSI* (arXiv:2007.11976) and the
*Design Space Exploration of NN Activation Function Circuits*
(arXiv:1810.08650) sweep spline / piecewise-linear / piecewise-polynomial
/ rational schemes against accuracy, area and latency jointly. This
module is the registry that makes the whole stack scheme-generic: every
consumer (Pallas epilogue kernels, the ActivationEngine, error analysis,
the gate-count model, the design-space explorer) programs against three
things:

  * ``ApproxSpec`` — the hashable static geometry of an approximant
    (generalizing the epilogue subsystem's ``TableSpec``): scheme name,
    LUT depth / polynomial degree, domain, odd symmetry, fixed-point
    format. Safe as a jit static argument and closable by kernel bodies.
  * ``build(spec, target)`` — host-side (numpy, float64 fit) parameter
    construction, returning ONE flat float32 2D array per scheme so the
    parameters ride into kernels as one SMEM operand:
        cr_spline  [depth, 4]       CR control-point windows
        pwl        [depth, 2]       segment (value, delta) pairs
        poly       [depth, deg+1]   per-segment Horner coefficients
        rational   [3, K]           Padé num/den in u = x^2 + Newton seed
  * ``block(v, params, spec)`` — the pure f32 datapath on an array,
    usable both as the NumPy/JAX reference (error analysis, custom-VJP
    recompute) and verbatim inside Pallas kernel bodies (element-wise
    ops only: table reads by a select chain in kernels or ``jnp.take``
    under XLA, Horner chains, a Newton reciprocal loop — no divide unit
    anywhere).

Registered schemes and their hardware analogues:

  cr_spline   the paper: Catmull-Rom LUT windows + integer-coefficient
              basis MAC. The block itself lives in
              ``kernels/epilogue.py::_cr_tanh_block`` (pinned there by
              the subsystem-layout test) and is re-exported here.
  pwl         PLAN-style segment LUT + one slope MAC (the paper's
              baseline, as deployable hardware rather than an oracle).
  poly        piecewise polynomial, Chebyshev-node fit per segment
              (near-minimax), evaluated in Horner form — a coefficient
              LUT feeding a ``degree``-stage MAC chain.
  rational    Padé approximant from the tanh continued fraction
              (odd truncation orders only — those are the monotone,
              saturating branch), with the reciprocal computed by a
              seeded Newton iteration: two multipliers and a subtractor
              per step, no divider, matching VLSI practice.

Adding a scheme is one ``@register`` class with ``build``/``block``; the
kernels, engine, analysis and DSE sweep pick it up by name.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable

import jax.numpy as jnp
import numpy as np

from . import catmull_rom as cr
from .fixed_point import GUARD_BITS, QFormat, fx_mul_shift, quantize, sat

# Newton-iteration count for the rational scheme's reciprocal. With the
# equioscillating linear seed built into the params (error E < 0.6 for
# every domain this repo sweeps), 5 iterations square the error to
# E^32 < 1e-7 — below f32 resolution, with zero divide hardware.
NEWTON_ITERS = 5


@dataclasses.dataclass(frozen=True)
class ApproxSpec:
    """Static geometry of an approximant (everything but the params).

    Generalizes the epilogue subsystem's ``TableSpec`` (which is now an
    alias of this class): hashable, so it can be a static argument of
    jitted wrappers and be closed over by Pallas kernel bodies, while
    the scheme's flat f32 parameter array rides along as a normal SMEM
    operand. ``period`` is kept as a real field (not a property) so CR
    specs built from a ``SplineTable`` carry the table's own float
    period bit-for-bit.
    """

    period: float | None = None   # segment width; None -> x_max / depth
    depth: int = 32               # LUT segments (cr_spline / pwl / poly)
    x_max: float = 4.0            # approximation domain [0, x_max)
    saturation: float = 0.999329299739067   # output at/beyond x_max
    scheme: str = "cr_spline"
    degree: int = 3               # poly: per-segment degree;
                                  # rational: continued-fraction order
    odd: bool = True              # odd-symmetric target (tanh family)
    int_bits: int = 2             # fixed-point format of the hardware
    frac_bits: int = 13           # datapath this spec models (Q2.13)

    def __post_init__(self):
        if self.period is None:
            object.__setattr__(self, "period", self.x_max / self.depth)

    @property
    def inv_period(self) -> float:
        return 1.0 / self.period

    @property
    def qformat(self) -> QFormat:
        """The fixed-point format this spec's hardware datapath carries
        (now swept geometry, not just the paper's Q2.13 constant)."""
        return QFormat(self.int_bits, self.frac_bits)

    @property
    def guard_format(self) -> QFormat:
        """Coefficient-ROM format of MAC-chain schemes: GUARD_BITS extra
        fraction bits below the datapath LSB."""
        return QFormat(self.int_bits, self.frac_bits + GUARD_BITS)

    @property
    def t_bits(self) -> int:
        """Low bits of the input magnitude forming the local t — the
        paper's index/t bit-slice, shared by every LUT scheme's fixed
        datapath. Requires one period to be a power-of-two number of
        LSBs (power-of-two depth over a power-of-two domain)."""
        t_scaled = self.period * self.qformat.scale
        tb = int(round(np.log2(t_scaled)))
        if 2 ** tb != int(round(t_scaled)):
            raise ValueError(
                f"period {self.period} is not a power-of-two number of "
                f"LSBs in {self.qformat} — the fixed datapath's index/t "
                f"bit-slice needs pow2 depth over a pow2 domain")
        return tb

    @classmethod
    def of(cls, table: cr.SplineTable) -> "ApproxSpec":
        """The CR spec of a built spline table (TableSpec back-compat)."""
        return cls(period=table.period, depth=table.depth,
                   x_max=table.x_max, saturation=table.saturation,
                   scheme="cr_spline")


# ---------------------------------------------------------------------------
# targets: the scalar functions approximants are built against
# ---------------------------------------------------------------------------

# target name -> (numpy fn on [0, x_max], odd symmetric?)
TARGETS: dict[str, tuple[Callable, bool]] = {
    "tanh": (np.tanh, True),
    # the softplus epilogue's even residual h(u) = log(1 + e^-u)
    "softplus_res": (lambda u: np.log1p(np.exp(-u)), False),
}


def _target_fn(target: str) -> Callable:
    try:
        return TARGETS[target][0]
    except KeyError:
        raise ValueError(f"unknown approximant target {target!r}; "
                         f"have {sorted(TARGETS)}") from None


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, "Approximant"] = {}


def register(cls):
    """Class decorator: instantiate and register an Approximant."""
    inst = cls()
    _REGISTRY[inst.scheme] = inst
    return cls


def schemes() -> tuple[str, ...]:
    """All registered scheme names (registration order)."""
    return tuple(_REGISTRY)


def get(scheme: str) -> "Approximant":
    try:
        return _REGISTRY[scheme]
    except KeyError:
        raise ValueError(f"unknown approximant scheme {scheme!r}; "
                         f"registered: {sorted(_REGISTRY)}") from None


class Approximant:
    """One approximation scheme: spec defaults + params + datapath."""

    scheme: str = "?"
    hardware = "?"                # one-line analogue for the README table
    # representative geometry for sweeps/tests (the registry-derived
    # default, so ablation / reduced DSE / contract tests pick up a new
    # scheme without hand-maintained tables)
    default_geometry: dict = {}

    def spec(self, target: str = "tanh", *, x_max: float = 4.0,
             depth: int = 32, degree: int = 3, int_bits: int = 2,
             frac_bits: int = 13) -> ApproxSpec:
        fn = _target_fn(target)          # curated error for unknown targets
        odd = TARGETS[target][1]
        return ApproxSpec(
            depth=depth, x_max=x_max,
            saturation=float(fn(np.asarray([x_max], np.float64))[0]),
            scheme=self.scheme, degree=degree, odd=odd,
            int_bits=int_bits, frac_bits=frac_bits)

    def params_shape(self, spec: ApproxSpec) -> tuple[int, int]:
        raise NotImplementedError

    def build(self, spec: ApproxSpec, target: str = "tanh") -> np.ndarray:
        """Host-side parameter construction (float64 fit -> f32 array)."""
        raise NotImplementedError

    def block(self, v, params, spec: ApproxSpec, *, lookup: str = "take",
              odd: bool | None = None):
        """Pure f32 datapath on an array (reference AND kernel body)."""
        raise NotImplementedError

    def build_fixed(self, spec: ApproxSpec, target: str = "tanh") -> np.ndarray:
        """Integer parameter ROM (int32 lattice) of the scheme's fixed
        datapath. Default: the float params quantized to the guard-bit
        coefficient format — the MAC-chain schemes' ROM; LUT-value
        schemes (cr_spline, pwl) override to quantize at the datapath
        format itself."""
        gfmt = spec.guard_format
        return np.asarray(quantize(
            self.build(spec, target).astype(np.float64), gfmt))

    def fixed_block(self, vq, params_q, spec: ApproxSpec):
        """Bit-accurate integer datapath: int32 lattice in (``spec.qformat``),
        int32 lattice out — the Fig.-3-style circuit of this scheme."""
        raise NotImplementedError

    def requantize(self, params, spec: ApproxSpec):
        """Traceable analogue of ``build_fixed`` on a (possibly trained)
        f32 parameter array: f32 params -> the int32 ROM ``fixed_block``
        reads. Default mirrors the default ``build_fixed`` (guard-format
        quantization of the float coefficients); LUT-value schemes
        override to match their own ROM construction. At the built
        (untrained) params this reproduces ``build_fixed`` exactly —
        asserted per scheme in tests — which is what makes the
        quantization-aware ``*_fixed`` training path consistent with the
        frozen integer datapath."""
        return quantize(jnp.asarray(params, jnp.float32), spec.guard_format)


def spec_for(scheme: str, act: str = "tanh", *, x_max: float = 4.0,
             depth: int = 32, degree: int = 3, int_bits: int = 2,
             frac_bits: int = 13) -> ApproxSpec:
    """The spec an *epilogue* reads: tanh-family epilogues share one
    tanh approximant; softplus uses the even residual target with the
    same widening the engine's jnp path applies (x_max >= 8, depth >=
    64) so every backend agrees on table contents."""
    if act == "softplus":
        return get(scheme).spec("softplus_res", x_max=max(x_max, 8.0),
                                depth=max(depth, 64), degree=degree,
                                int_bits=int_bits, frac_bits=frac_bits)
    return get(scheme).spec("tanh", x_max=x_max, depth=depth, degree=degree,
                            int_bits=int_bits, frac_bits=frac_bits)


def target_of(act: str) -> str:
    """Epilogue name -> approximant target name."""
    return "softplus_res" if act == "softplus" else "tanh"


@lru_cache(maxsize=None)
def params_for(spec: ApproxSpec, target: str = "tanh") -> np.ndarray:
    """Cached ``build`` (specs are hashable; params are host numpy)."""
    return get(spec.scheme).build(spec, target)


def block(v, params, spec: ApproxSpec, *, lookup: str = "take",
          odd: bool | None = None):
    """Generic datapath dispatch — the single entry point kernels and
    references share."""
    return get(spec.scheme).block(v, params, spec, lookup=lookup, odd=odd)


def reference(x, spec: ApproxSpec, target: str = "tanh"):
    """Approximate ``target`` at x via ``spec`` (pure jnp, f32 params)."""
    y = block(x.astype(jnp.float32) if hasattr(x, "astype") else
              jnp.asarray(x, jnp.float32),
              jnp.asarray(params_for(spec, target)), spec)
    return y.astype(jnp.asarray(x).dtype)


@lru_cache(maxsize=None)
def fixed_params_for(spec: ApproxSpec, target: str = "tanh") -> np.ndarray:
    """Cached integer ROM of ``spec``'s fixed datapath (host numpy int32)."""
    return get(spec.scheme).build_fixed(spec, target)


def fixed_block(vq, params_q, spec: ApproxSpec):
    """Generic bit-accurate datapath dispatch: int32 ``spec.qformat``
    lattice in/out. The fixed-point analogue of ``block`` — the single
    entry point error analysis and the ``<scheme>_fixed`` engine
    backends share."""
    return get(spec.scheme).fixed_block(vq, params_q, spec)


def requantize(params, spec: ApproxSpec):
    """Generic traceable f32-params -> int32-ROM dispatch (the trainable
    analogue of ``fixed_params_for``): what the bound ``<scheme>_fixed``
    engine backends feed ``fixed_block`` during quantization-aware
    training."""
    return get(spec.scheme).requantize(params, spec)


# ---------------------------------------------------------------------------
# shared datapath pieces (Pallas-safe: element-wise + tiny gathers only)
# ---------------------------------------------------------------------------

def _index_t_split(av, spec: ApproxSpec):
    """|x| -> (segment index int32, local t in [0,1)) — the paper's
    bit-slice, as one float multiply + floor (shared by every LUT
    scheme so index geometry is identical across the design space)."""
    u = av * spec.inv_period
    k = jnp.clip(jnp.floor(u), 0.0, spec.depth - 1.0)
    return k.astype(jnp.int32), u - k


def _gather_columns(tableau, ki, lookup: str):
    """Row-gather of a [depth, C] f32 tableau at int32 indices ``ki``
    (already clipped to [0, depth)). Returns a tuple of C arrays shaped
    like ``ki``; both lookups pick the same entries exactly.

    ``select`` walks the rows: one compare and C selects per row, with
    the tableau read as scalars. Inside a kernel the tableau is the SMEM
    ref, so each read is a scalar load and every vector op stays 2-D —
    Mosaic lowers neither a vector gather nor a [.., depth] one-hot
    operand at real block sizes. ``take`` is XLA's gather: the jnp
    reference and the custom-VJP backward.
    """
    depth, ncols = tableau.shape
    if lookup == "select":
        cols = [jnp.full(ki.shape, tableau[0, c], jnp.float32)
                for c in range(ncols)]
        for d in range(1, depth):
            hit = ki == d
            cols = [jnp.where(hit, tableau[d, c], col)
                    for c, col in enumerate(cols)]
        return tuple(cols)
    if lookup == "take":
        return tuple(jnp.take(tableau[:, c], ki) for c in range(ncols))
    raise ValueError(f"unknown lookup {lookup!r}")


def _finish(y, v, av, spec: ApproxSpec, odd: bool):
    """Shared epilogue of every scheme: clamp at the domain edge to the
    saturation constant, then restore the sign for odd targets."""
    y = jnp.where(av >= spec.x_max, jnp.float32(spec.saturation), y)
    if odd:
        y = jnp.where(v < 0.0, -y, y)
    return y


# ---------------------------------------------------------------------------
# shared fixed-datapath pieces (int32 lattice; see core/fixed_point.py)
# ---------------------------------------------------------------------------

def _sat_q(spec: ApproxSpec) -> int:
    """The saturation constant on the output lattice (a wired constant
    in hardware). Pure numpy — callable at trace time — and identical
    to fixed_point.quantize's host path / build_fixed_table's sat_q."""
    fmt = spec.qformat
    q = np.round(np.float64(spec.saturation) * fmt.scale)
    return int(np.clip(q, fmt.min_int, fmt.max_int))


def _fixed_front(vq, spec: ApproxSpec):
    """The integer front-end every LUT scheme shares (paper Fig. 3):
    sign strip, |x|, index/t bit-slice, domain-range compare. Returns
    (sign_neg, idx clipped int32, in_range, t_q raw residue)."""
    vq = jnp.asarray(vq, jnp.int32)
    tb = spec.t_bits
    sign_neg = vq < 0
    mag = jnp.abs(vq)
    idx = (mag >> tb).astype(jnp.int32)
    in_range = idx < spec.depth
    idx_c = jnp.clip(idx, 0, spec.depth - 1)
    t_q = mag & ((1 << tb) - 1)
    return sign_neg, idx_c, in_range, t_q


def _fixed_finish(y, sign_neg, in_range, spec: ApproxSpec):
    """Saturation mux + odd-symmetry sign restore on the lattice."""
    y = jnp.where(in_range, y, jnp.int32(_sat_q(spec)))
    if spec.odd:
        y = jnp.where(sign_neg, -y, y)
    return y


# ---------------------------------------------------------------------------
# scheme: cr_spline (the paper)
# ---------------------------------------------------------------------------

@register
class CRSpline(Approximant):
    """Catmull-Rom spline LUT (the paper's Fig. 2/3 unit).

    The authoritative block implementation is
    ``kernels/epilogue.py::_cr_tanh_block`` — the subsystem-layout test
    pins the single definition there; this class adapts it to the
    registry API (bit-for-bit: same function object)."""

    scheme = "cr_spline"
    hardware = "CR window LUT + integer-coeff basis MAC (paper Fig. 2/3)"
    default_geometry = {"depth": 32}

    def params_shape(self, spec):
        return (spec.depth, 4)

    def build(self, spec, target="tanh"):
        tab = cr.build_table(_target_fn(target), spec.x_max, spec.depth,
                             saturation=spec.saturation)
        return np.asarray(tab.windows, np.float32)

    def block(self, v, params, spec, *, lookup="take", odd=None):
        from repro.kernels.epilogue import _cr_tanh_block  # layout-pinned
        return _cr_tanh_block(v, params, spec=spec, lookup=lookup,
                              odd=spec.odd if odd is None else odd)

    def build_fixed(self, spec, target="tanh"):
        # quantized from the float64 knot table, EXACTLY as
        # build_fixed_table does — the CR fixed route must stay
        # bit-identical to the pre-registry Fig. 3 emulation
        ftab = cr.build_fixed_table(_target_fn(target), spec.x_max,
                                    spec.depth, spec.qformat)
        return np.asarray(ftab.windows_q)

    def fixed_block(self, vq, params_q, spec):
        # the authoritative CR integer datapath is
        # catmull_rom.interpolate_fixed; adapt it to the registry API
        # (same index geometry: FixedTable.t_bits == spec.t_bits).
        # Wide geometries (t_bits > 10: depth 8/16 at Q2.13, any depth
        # <= 64 at Q2.16) run the exact int32 limb MAC — every depth
        # is jit/TPU-legal, no int64 anywhere.
        ftab = cr.FixedTable(spec.qformat, spec.x_max, spec.depth,
                             spec.t_bits, params_q, _sat_q(spec))
        return cr.interpolate_fixed(ftab, vq)

    def requantize(self, params, spec):
        # window values quantized straight to the OUTPUT lattice —
        # exactly what build_fixed_table does to the f64 knot windows
        return quantize(jnp.asarray(params, jnp.float32), spec.qformat)


# ---------------------------------------------------------------------------
# scheme: pwl (PLAN-style segment LUT + slope MAC)
# ---------------------------------------------------------------------------

@register
class PWL(Approximant):
    """Piecewise-linear over uniform knots: one LUT row (value, delta)
    per segment and a single multiplier — y = y0 + t * (y1 - y0). The
    deltas are precomputed host-side (hardware: a second LUT column),
    so the datapath is one MAC, the cheapest deployable point in the
    design space."""

    scheme = "pwl"
    hardware = "value+delta LUT, single slope MAC (PLAN-style)"
    default_geometry = {"depth": 32}

    def params_shape(self, spec):
        return (spec.depth, 2)

    def build(self, spec, target="tanh"):
        fn = _target_fn(target)
        ks = np.arange(spec.depth + 1, dtype=np.float64) * spec.period
        y = fn(ks)
        out = np.stack([y[:-1], np.diff(y)], axis=1)
        return np.asarray(out, np.float32)

    def block(self, v, params, spec, *, lookup="take", odd=None):
        odd = spec.odd if odd is None else odd
        av = jnp.abs(v) if odd else v
        ki, t = _index_t_split(av, spec)
        y0, dy = _gather_columns(params, ki, lookup)
        return _finish(y0 + t * dy, v, av, spec, odd)

    def build_fixed(self, spec, target="tanh"):
        # knots quantized to the OUTPUT lattice, deltas formed on the
        # lattice (y_q[k+1] - y_q[k]) so segment ends land exactly on
        # the quantized knots — the hardware's second LUT column
        fn = _target_fn(target)
        ks = np.arange(spec.depth + 1, dtype=np.float64) * spec.period
        yq = np.asarray(quantize(fn(ks), spec.qformat))
        return np.stack([yq[:-1], np.diff(yq)], axis=1).astype(np.int32)

    def fixed_block(self, vq, params_q, spec):
        # the integer value+delta MAC: y = y0 + (t_q * dy) >>r t_bits,
        # one product with a rounding adder folded into the shift
        sign_neg, idx, in_range, t_q = _fixed_front(vq, spec)
        tb = spec.t_bits
        y0 = jnp.take(params_q[:, 0], idx)
        dy = jnp.take(params_q[:, 1], idx)
        # |dy| <= slope * period on the lattice: tb+1 bits covers every
        # target with |f'| <= 1 (tanh family and the softplus residual)
        step = fx_mul_shift(dy, t_q, tb, rounding="nearest",
                            a_bits=tb + 1, b_bits=tb)
        y = sat(y0 + step, spec.qformat)
        return _fixed_finish(y, sign_neg, in_range, spec)

    def requantize(self, params, spec):
        # reconstruct the knot values from (value, delta), quantize the
        # knots to the OUTPUT lattice, re-form the deltas ON the lattice
        # — the same order of operations as build_fixed, so segment ends
        # land exactly on the quantized knots after training too
        p = jnp.asarray(params, jnp.float32)
        knots = jnp.concatenate([p[:, 0], p[-1:, 0] + p[-1:, 1]])
        yq = quantize(knots, spec.qformat)
        return jnp.stack([yq[:-1], yq[1:] - yq[:-1]], axis=1)


# ---------------------------------------------------------------------------
# scheme: poly (piecewise near-minimax polynomial, Horner)
# ---------------------------------------------------------------------------

@register
class PiecewisePoly(Approximant):
    """Per-segment polynomial in the local coordinate t in [0, 1),
    endpoint-interpolating with interior Chebyshev nodes, evaluated in
    Horner form: a [depth, degree+1] coefficient LUT feeding ``degree``
    fused MACs. This is the DCTIF-style middle of the design space:
    more multipliers than PWL, fewer table bits than a deep spline.

    The fit pins both segment endpoints to the target exactly —
    p(t) = f(a) + (f(b)-f(a)) t + t(1-t) r(t), with r interpolating the
    residual at degree-1 interior Chebyshev nodes. Pinning costs a
    near-minimax constant factor but buys the hardware-unit contract:
    the piecewise function is continuous by construction (a free-fit
    version had boundary jumps that broke monotonicity at coarse
    geometries), odd targets hit exactly 0 at 0, and the unit stays
    monotone over the whole Q2.13 lattice at every swept geometry
    (enforced by the design-contract tests)."""

    scheme = "poly"
    hardware = "coeff LUT + degree-stage Horner MAC chain (DCTIF-style)"
    default_geometry = {"depth": 8, "degree": 3}

    def params_shape(self, spec):
        return (spec.depth, spec.degree + 1)

    def build(self, spec, target="tanh"):
        fn = _target_fn(target)
        deg = spec.degree
        if deg < 1:
            raise ValueError(f"poly needs degree >= 1, got {deg}")
        out = np.empty((spec.depth, deg + 1), np.float64)
        j = np.arange(max(deg - 1, 1), dtype=np.float64)
        tnodes = 0.5 * (1.0 - np.cos((2 * j + 1) * np.pi
                                     / (2 * max(deg - 1, 1))))
        for k in range(spec.depth):
            a = k * spec.period
            fa = float(fn(np.float64(a)))
            fb = float(fn(np.float64(a + spec.period)))
            if deg == 1:                     # endpoint line (PWL-equal)
                out[k] = [fb - fa, fa]
                continue
            ys = fn(a + tnodes * spec.period)
            lin = fa + (fb - fa) * tnodes
            r = np.polyfit(tnodes, (ys - lin) / (tnodes * (1.0 - tnodes)),
                           deg - 2)
            # p = fa + (fb-fa) t + t(1-t) r(t), expanded to power basis
            p = np.polymul(np.atleast_1d(r), [-1.0, 1.0, 0.0])
            base = np.zeros(deg + 1)
            base[-1], base[-2] = fa, fb - fa
            p = np.polyadd(p, base)
            out[k] = np.pad(p, (deg + 1 - len(p), 0))
        return np.asarray(out, np.float32)   # highest power first

    def block(self, v, params, spec, *, lookup="take", odd=None):
        odd = spec.odd if odd is None else odd
        av = jnp.abs(v) if odd else v
        ki, t = _index_t_split(av, spec)
        coeffs = _gather_columns(params, ki, lookup)
        y = coeffs[0]
        for c in coeffs[1:]:                 # Horner, degree static
            y = y * t + c
        return _finish(y, v, av, spec, odd)

    def fixed_block(self, vq, params_q, spec):
        # truncating Horner chain over the guard-bit coefficient ROM:
        # each MAC stage is (acc * t_q) >> t_bits (a plain wire shift —
        # truncation, as synthesized MAC chains do) plus the next ROM
        # coefficient, all in the guard format; ONE rounding shift at
        # the end drops the guard bits into the output register
        sign_neg, idx, in_range, t_q = _fixed_front(vq, spec)
        tb = spec.t_bits
        gfmt = spec.guard_format
        acc_bits = spec.int_bits + gfmt.frac_bits + 1
        acc = jnp.take(params_q[:, 0], idx)
        for j in range(1, spec.degree + 1):
            step = fx_mul_shift(t_q, acc, tb, rounding="floor",
                                a_bits=tb, b_bits=acc_bits)
            acc = sat(step + jnp.take(params_q[:, j], idx), gfmt)
        y = sat((acc + (1 << (GUARD_BITS - 1))) >> GUARD_BITS, spec.qformat)
        return _fixed_finish(y, sign_neg, in_range, spec)


# ---------------------------------------------------------------------------
# scheme: rational (Padé + Newton reciprocal, no divide unit)
# ---------------------------------------------------------------------------

def _pade_from_cf(order: int) -> tuple[np.ndarray, np.ndarray]:
    """Padé num/den polynomials in u = x^2 from the tanh continued
    fraction  tanh(x) = x / (1 + u/(3 + u/(5 + ...)))  truncated at
    ``order`` levels:  tanh ~= x * num(u) / den(u).  Coefficients are
    float64, lowest power first, NOT yet normalized."""
    # R_k = N_k / D_k with R_order = [2*order - 1]; descend via
    # R_k = (2k-1) + u / R_{k+1} = ((2k-1) N_{k+1} + u D_{k+1}) / N_{k+1}
    n = np.array([2.0 * order - 1.0])
    d = np.array([1.0])
    for k in range(order - 1, 0, -1):
        u_d = np.concatenate([[0.0], d])     # u * D_{k+1}
        width = max(len(n), len(u_d))
        new_n = (2.0 * k - 1.0) * np.pad(n, (0, width - len(n)))
        new_n = new_n + np.pad(u_d, (0, width - len(u_d)))
        n, d = new_n, n
    return d, n                              # tanh ~= x * D_1 / N_1


@register
class PadeRational(Approximant):
    """Padé approximant of tanh with a Newton-iteration reciprocal.

    Only odd continued-fraction orders are exposed: those convergents
    have equal num/den degree in u, so x*num/den grows monotonically
    through the saturation clamp (even orders peak *inside* [0, x_max]
    and would break the design contract that every registered scheme is
    monotone). ``degree`` is rounded up to the next odd order >= 3.

    The reciprocal is computed the way VLSI does it without a divider:
    a linear equioscillating seed r0 = alpha - beta*den (two constants,
    baked into the params at build time) followed by NEWTON_ITERS
    iterations r <- r * (2 - den * r) — two multipliers and a
    subtractor per stage. Denominator range [den(0)=1, den(x_max^2)]
    bounds the seed error below 0.6, so 5 iterations land under f32
    resolution.

    Params layout [3, K]: row 0 num coeffs (u^0..), row 1 den coeffs,
    row 2 [alpha, beta, 0...] — one flat SMEM operand like every other
    scheme. Padé targets tanh only; the softplus residual has no odd
    continued fraction, so ``build`` rejects it with a clear error
    (softplus under the rational scheme needs a table-based residual —
    use pwl/poly/cr_spline for that epilogue).
    """

    scheme = "rational"
    hardware = "Pade num/den Horner + seeded Newton reciprocal (no divider)"
    default_geometry = {"degree": 5}

    @staticmethod
    def _order(degree: int) -> int:
        order = max(int(degree), 3)
        return order if order % 2 == 1 else order + 1

    def params_shape(self, spec):
        order = self._order(spec.degree)
        return (3, order // 2 + 1)           # den degree in u = (order-1)/2

    def build(self, spec, target="tanh"):
        if target != "tanh":
            raise ValueError(
                "rational (Pade) approximant targets tanh only; the "
                f"softplus residual {target!r} needs a table-based scheme "
                "(cr_spline / pwl / poly)")
        order = self._order(spec.degree)
        num, den = _pade_from_cf(order)
        num, den = num / den[0], den / den[0]        # den(0) = 1
        k = max(len(num), len(den), 2)
        # equioscillating linear seed for 1/den on [1, D]
        big_d = float(np.polyval(den[::-1], spec.x_max ** 2))
        beta = 8.0 / (4.0 * big_d + (big_d + 1.0) ** 2)
        alpha = beta * (big_d + 1.0)
        out = np.zeros((3, k), np.float64)
        out[0, :len(num)] = num
        out[1, :len(den)] = den
        out[2, :2] = (alpha, beta)
        return np.asarray(out, np.float32)

    def block(self, v, params, spec, *, lookup="take", odd=None):
        del lookup                           # no LUT: pure arithmetic
        odd = spec.odd if odd is None else odd
        av = jnp.abs(v) if odd else v
        avc = jnp.minimum(av, jnp.float32(spec.x_max))   # keep den in range
        u = avc * avc
        k = params.shape[1]
        num = params[0, k - 1]
        den = params[1, k - 1]
        for j in range(k - 2, -1, -1):       # Horner in u, static unroll
            num = num * u + params[0, j]
            den = den * u + params[1, j]
        num = num * avc
        r = params[2, 0] - params[2, 1] * den    # linear seed for 1/den
        for _ in range(NEWTON_ITERS):
            r = r * (2.0 - den * r)
        # clamp Pade overshoot at the saturation constant: odd CF
        # convergents are increasing, so min() keeps monotonicity
        y = jnp.minimum(num * r, jnp.float32(spec.saturation))
        return _finish(y, v, av, spec, odd)

    def _internal_int_bits(self, spec) -> int:
        """Integer bits of the chain's internal format: wide enough for
        den(x_max^2) (the largest value the datapath carries), computed
        host-side from the same continued fraction the params bake in."""
        order = self._order(spec.degree)
        num, den = _pade_from_cf(order)
        big_d = float(np.polyval((den / den[0])[::-1], spec.x_max ** 2))
        return max(spec.int_bits, int(np.ceil(np.log2(big_d))) + 1)

    def fixed_block(self, vq, params_q, spec):
        # the integer Padé + Newton-reciprocal chain. Everything runs in
        # an internal guard format Q<gI>.<frac+GUARD_BITS> whose integer
        # width gI covers den(x_max^2); each product is one wide MAC
        # with a rounding adder folded into its single output shift
        # (truncating MACs measurably cost one extra LSB at high CF
        # orders). fx_mul_shift picks the exact int32 lowering — the
        # wide den/Newton products use the 4-piece partial-product
        # decomposition, so the whole chain is jit/TPU-legal with no
        # int64 anywhere.
        fmt = spec.qformat
        gfmt = spec.guard_format
        gf = gfmt.frac_bits
        ifmt = QFormat(self._internal_int_bits(spec), gf)
        w = ifmt.int_bits + gf + 1           # operand magnitude bound
        vq = jnp.asarray(vq, jnp.int32)
        sign_neg = vq < 0
        mag = jnp.abs(vq)
        xmax_q = int(round(spec.x_max * fmt.scale))
        in_range = mag < xmax_q
        avc = jnp.minimum(mag, xmax_q)       # keep den in range
        in_b = spec.int_bits + spec.frac_bits + 1
        # u = x^2 straight into the guard format: one squarer, shift
        # 2*frac - (frac+G) = frac - G (needs frac_bits > GUARD_BITS)
        if spec.frac_bits <= GUARD_BITS:
            raise ValueError(
                f"rational fixed datapath needs frac_bits > {GUARD_BITS} "
                f"guard bits, got {spec.qformat}")
        u = fx_mul_shift(avc, avc, spec.frac_bits - GUARD_BITS,
                         rounding="nearest", a_bits=in_b, b_bits=in_b)
        u_bits = 2 * spec.int_bits + gf + 1
        k = params_q.shape[1]
        num = params_q[0, k - 1]
        den = params_q[1, k - 1]
        for j in range(k - 2, -1, -1):       # two Horner chains in u
            num = sat(fx_mul_shift(num, u, gf, rounding="nearest",
                                   a_bits=w, b_bits=u_bits)
                      + params_q[0, j], ifmt)
            den = sat(fx_mul_shift(den, u, gf, rounding="nearest",
                                   a_bits=w, b_bits=u_bits)
                      + params_q[1, j], ifmt)
        # seeded Newton reciprocal: r <- r * (2 - den * r), no divider
        two_g = 2 << gf
        r = sat(params_q[2, 0]
                - fx_mul_shift(params_q[2, 1], den, gf, rounding="nearest",
                               a_bits=gf + 2, b_bits=w), ifmt)
        for _ in range(NEWTON_ITERS):
            dr = fx_mul_shift(den, r, gf, rounding="nearest",
                              a_bits=w, b_bits=w)
            r = sat(fx_mul_shift(r, two_g - dr, gf, rounding="nearest",
                                 a_bits=w, b_bits=w), ifmt)
        ratio = sat(fx_mul_shift(num, r, gf, rounding="nearest",
                                 a_bits=w, b_bits=w), ifmt)
        # final multiplier drops back to the output lattice; clamp the
        # Padé overshoot at the saturation constant (monotone branch)
        y = fx_mul_shift(ratio, avc, gf, rounding="nearest",
                         a_bits=gf + 2, b_bits=in_b)
        y = sat(jnp.minimum(y, _sat_q(spec)), fmt)
        return _fixed_finish(y, sign_neg, in_range, spec)
