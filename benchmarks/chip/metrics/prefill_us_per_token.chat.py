"""Model step (prefill): device time of the jit_prefill_chunk runs in the traced window over the prompt tokens of their engine.dispatch kind=prefill spans (us)."""
from chipbench import program_trace


def read(run):
    return program_trace.prefill_us_per_token(run)
