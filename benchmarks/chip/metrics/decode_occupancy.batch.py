"""Engine/scheduler: decode tokens over decode steps x slots in the window (%)."""
from chipbench import readers


def read(run):
    return readers.decode_occupancy(run)
