"""Least time of the glu_2d calls issued in the traced window over the device time of the kernel's events there (%)."""
from chipbench import readers


def read(run):
    return readers.glu_roofline(run)
