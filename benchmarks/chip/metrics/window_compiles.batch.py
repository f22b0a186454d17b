"""JIT / compile cache: backend compiles inside the window (count)."""
from chipbench import readers


def read(run):
    return readers.window_compiles(run)
