"""Fused GLU matmuls + CR-spline activation: the GLU instance of the
shared epilogue kernel-builder (see ``epilogue.py``).

    out = epilogue(x @ w_gate) * (x @ w_up)

Memory traffic per (bm, bn) output tile:  x once per K-step, both weight
tiles once, ONE output write — vs. three HBM round-trips (gate, up,
product) for the unfused version. For d_ff-sized GLUs this removes
~2/3 of activation bytes in the FFN forward pass.

Kept as a module for API stability — the CR-tanh block and the kernel
body live in ``epilogue``; this file only re-binds the entry point.
"""
from __future__ import annotations

from .epilogue import (  # noqa: F401  (re-exported: shared datapath)
    EPILOGUES,
    TableSpec,
    _cr_tanh_block,
    glu_2d,
)


def fused_glu_2d(x, w_gate, w_up, windows, *, period: float, x_max: float,
                 saturation: float, act: str = "silu",
                 block_m: int = 128, block_n: int = 128, block_k: int = 512):
    """out[M,N] = act_cr(x[M,K] @ w_gate[K,N]) * (x @ w_up). Dims must be
    divisible by the block shape (`ops.fused_glu` pads)."""
    spec = TableSpec(period=period, depth=windows.shape[0], x_max=x_max,
                     saturation=saturation)
    return glu_2d(x, w_gate, w_up, windows, spec=spec, act=act,
                  block_m=block_m, block_n=block_n, block_k=block_k)
