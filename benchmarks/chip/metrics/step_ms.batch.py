"""Model step: mean host time of engine.step() calls that did work in the window (ms)."""
from chipbench import readers


def read(run):
    return readers.step_ms(run)
