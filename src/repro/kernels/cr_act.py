"""Element-wise CR-spline tanh: the matmul-free instance of the shared
epilogue kernel-builder (see ``epilogue.py`` for the datapath notes).

Kept as a module for API stability — the CR-tanh block itself lives in
``epilogue._cr_tanh_block``; this file only binds ``act="tanh"``.
"""
from __future__ import annotations

from .epilogue import (  # noqa: F401  (re-exported: public tuning knobs)
    DEFAULT_BLOCK_COLS,
    DEFAULT_BLOCK_ROWS,
    TableSpec,
    _basis_weights_f32,
    _cr_tanh_block,
    elementwise_2d,
)


def cr_act_2d(x, windows, *, period: float, x_max: float, saturation: float,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              block_cols: int = DEFAULT_BLOCK_COLS):
    """Apply the CR-spline tanh to a 2D array (rows, cols divisible by
    the block shape; `ops.cr_act` handles padding/reshaping)."""
    spec = TableSpec(period=period, depth=windows.shape[0], x_max=x_max,
                     saturation=saturation)
    return elementwise_2d(x, windows, spec=spec, act="tanh",
                          block_rows=block_rows, block_cols=block_cols)
