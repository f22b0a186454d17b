"""Model step (decode): device time of the jit_decode_chunk runs in the traced window over the decode steps of their engine.dispatch kind=decode spans (ms)."""
from chipbench import program_trace


def read(run):
    return program_trace.decode_step_ms(run)
