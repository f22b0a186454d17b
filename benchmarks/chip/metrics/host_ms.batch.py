"""Engine host loop: mean, over engine.step spans that did work in the traced window, of the span minus its engine.wait spans (ms)."""
from chipbench import program_trace


def read(run):
    return program_trace.host_ms_mean(run)
