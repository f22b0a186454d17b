"""95th percentile, over requests due in the window, of due time to first token on the host (ms)."""
from chipbench import readers


def read(run):
    return readers.ttft_p95_ms(run)
