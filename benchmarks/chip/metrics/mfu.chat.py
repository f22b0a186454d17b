"""Whole step: model FLOPs of the real tokens prefilled and decoded in the window over summed step() time x peak bf16 FLOP/s (%)."""
from chipbench import readers


def read(run):
    return readers.mfu(run)
