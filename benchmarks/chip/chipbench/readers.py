"""Arithmetic shared by the metric readers in ``metrics/``. Each reader
is a small file that calls one of these; a reader that finds nothing to
read returns None and its metric is left out of the result."""
from __future__ import annotations

import math

from . import flops, trace as tr
from .stats import percentile, rate, tpot

# How the fused GLU kernel's events are found in the device trace: a
# device event is named by its HLO instruction's text, and a Pallas
# kernel is a ``custom-call`` instruction (``custom_call_target=
# "tpu_custom_call"``), named after the ``lax.platform_dependent``
# branch around it (``%branch_0_fun.N = ... custom-call(...)``). In a
# configuration whose only Pallas kernel is ``glu_2d`` no other custom
# call is so named (XLA's own custom calls are ``custom-call.N``); the
# count check below holds it to that.
GLU_OPCODE = "custom-call"
GLU_INSTRUCTION = "branch_"


def is_glu_event(name: str) -> bool:
    inst, _, opcode = tr.hlo_parts(name)
    return opcode == GLU_OPCODE and inst.startswith(GLU_INSTRUCTION)


def ttft_p95_ms(run):
    due = run.due_in_window()
    if not due:
        return None
    return 1e3 * percentile([(r.first - r.due) if r.first is not None
                             else math.inf for r in due], 95)


def tpot_p95_ms(run):
    vals = []
    for r in run.due_in_window():
        if not r.done:
            vals.append(math.inf)
            continue
        v = tpot(r.first, r.last, len(r.tokens))
        if v is not None:
            vals.append(v)
    return 1e3 * percentile(vals, 95) if vals else None


def output_tok_s(run):
    return rate(run.tokens_in_window, *run.window)


def queue_wait_p95_ms(run):
    due = run.due_in_window()
    if not due:
        return None
    return 1e3 * percentile([(r.admitted - r.due) if r.admitted is not None
                             else math.inf for r in due], 95)


def decode_occupancy(run):
    c = run.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * c["decode_tokens"] / (c["decode_steps"] * run.slots)


def step_ms(run):
    steps = run.steps_in_window()
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)


def mfu(run):
    steps = run.steps_in_window()
    if not steps or run.peaks is None:
        return None
    busy = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * sum(s.flops for s in steps) / (
        busy * run.peaks.bf16_flops_per_s)


def device_idle(run):
    t = run.trace
    if t is None or t.hi <= t.lo:
        return None
    return 100.0 * (1.0 - tr.busy_ns(t.ops, t.lo, t.hi) / (t.hi - t.lo))


def window_compiles(run):
    return float(run.window_compiles)


def glu_roofline(run, log=print):
    """Least time of the fused-GLU calls the engine issued inside the
    traced window (rows M per call, K = hidden, N = intermediate) over
    the device time of the kernel's events there. The calls issued and
    the events found must agree in number: a trace that holds none of
    the kernel's events, or another number of them, is an error."""
    t = run.trace
    if t is None or run.peaks is None:
        return None
    secs, n_events = tr.kernel_time(t.ops, t.lo, t.hi, is_glu_event)
    K, N = run.conf["hidden_size"], run.conf["intermediate_size"]
    least, calls = 0.0, 0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for s in run.steps:
        if s.t0 >= t.t0 and s.t1 <= t.t1:
            for m, n in s.glu_rows:
                f, b = flops.glu_call(m, K, N)
                lt, bound = flops.least_time(f, b, run.peaks)
                least += n * lt
                by_bound[bound] += n * lt
                calls += n
    log(f"[glu_2d] calls issued={calls} trace events={n_events} "
        f"kernel_s={secs} least_s={least} least_by_bound={by_bound}")
    if not calls:
        return None
    if n_events != calls or secs <= 0:
        kinds = sorted({tr.short_name(n) for n, _, _ in t.ops
                        if GLU_OPCODE in n})[:20]
        raise RuntimeError(f"glu_2d: the engine issued {calls} calls in the "
                           f"traced window, the trace holds {n_events} "
                           f"{GLU_INSTRUCTION}* {GLU_OPCODE} events; events that name a "
                           f"{GLU_OPCODE}: {kinds}")
    return 100.0 * least / secs
