"""95th percentile, over requests due in the window, of (last token - first token) / (tokens - 1) (ms)."""
from chipbench import readers


def read(run):
    return readers.tpot_p95_ms(run)
