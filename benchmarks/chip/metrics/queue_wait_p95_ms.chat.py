"""Engine/scheduler: 95th percentile of due time to slot admission (Completion.admitted_at) over requests due in the window (ms)."""
from chipbench import readers


def read(run):
    return readers.queue_wait_p95_ms(run)
