"""The program's own host spans in a traced window.

The serving engine records its host spans (``engine.step`` holding
``engine.admit``, ``engine.pages``, one ``engine.dispatch`` per program
dispatched, one ``engine.wait`` per host block on the device, and
``engine.harvest``) on ``time.perf_counter()``, the harness's clock, in
a bounded buffer. The metric readers run after the engine is freed, so
they read the buffer of the process's newest recorder
(``repro.serve.spans.latest()``). From it this module gives, for the
traced window on the host's clock (``run.trace.t0``, ``t1``):

- per engine step that did work, the host time outside its waits;
- the host time of each kind of span, outside the spans nested in it;
- the device time of a program's runs (the first chip's ``XLA Modules``
  events, ``run.modules``, each paired in order with the dispatch span
  that enqueued it) over a count its dispatch spans carry: decode steps of a
  decode chunk, prompt tokens of a prefill chunk.

A program that records no spans (no ``repro.serve.spans``) gives None,
and so does every metric read from it. The functions on plain lists do
the arithmetic and are what the tests check.
"""
from __future__ import annotations

import importlib
import json
import sys


def engine_spans():
    """The spans of the newest recorder in this process, or None where
    the program records none."""
    try:
        spans = importlib.import_module("repro.serve.spans")
    except ImportError:
        return None
    rec = spans.latest()
    return None if rec is None else list(rec.spans)


def _in(spans, t0, t1, name=None):
    return [s for s in spans if (name is None or s.name == name)
            and t0 <= s.t0 and s.t1 <= t1]


def host_ms(spans, t0: float, t1: float) -> list:
    """Per ``engine.step`` inside [t0, t1] that did work (it decoded or
    prefilled), its duration minus the ``engine.wait`` spans inside it,
    in ms."""
    waits = _in(spans, t0, t1, "engine.wait")
    out = []
    for st in _in(spans, t0, t1, "engine.step"):
        if st.attrs.get("n_slots", 0) + st.attrs.get("n_chunks", 0) == 0:
            continue
        waited = sum(w.t1 - w.t0 for w in waits
                     if st.t0 <= w.t0 and w.t1 <= st.t1)
        out.append(1e3 * (st.t1 - st.t0 - waited))
    return out


def host_spans(spans, t0: float, t1: float) -> dict:
    """{name, or name:kind where the span has a kind: [count, seconds]}
    of the spans inside [t0, t1], each span's own time: its duration
    less that of the spans opened directly inside it."""
    inside = _in(spans, t0, t1)
    out: dict = {}
    for s in inside:
        kids = sum(c.t1 - c.t0 for c in inside if c.parent == s.name
                   and s.t0 <= c.t0 and c.t1 <= s.t1 and c is not s)
        key = s.name + (f":{s.attrs['kind']}" if "kind" in s.attrs else "")
        row = out.setdefault(key, [0, 0.0])
        row[0] += 1
        row[1] += s.t1 - s.t0 - kids
    return out


# How the engine's programs are named among the trace's program runs:
# ``jit_<function>(<fingerprint>)``, the function being the one the
# engine jits for that dispatch kind.
PROGRAMS = {"decode": "jit_decode_chunk", "prefill": "jit_prefill_chunk"}


def program_runs(modules, program: str) -> list:
    """(start, duration) in ns of every run of ``program`` in the trace,
    in the order they started."""
    return [(s, d) for n, s, d in sorted(modules, key=lambda e: e[1])
            if n.split("(", 1)[0] == program]


def pair_runs(spans, t, runs: list, kind: str, unit: str) -> list:
    """[(device ns, ``unit``)]: each ``engine.dispatch`` span of ``kind``
    inside the traced window [t.t0, t.t1] (a span that names another
    ``program`` enqueued another one) paired with the run it enqueued.

    The pairing is by order, and compares no clock of the device with
    the host's: the harness syncs the device before it starts the trace,
    so the trace's first run of the program is the one the first
    dispatch after ``t.t0`` enqueued, and one chip runs a program's
    dispatches in the order they came. The window's n dispatches are the
    first n since ``t.t0``, so their runs are the trace's first n. As a
    check on that count, the n-th run starts before the end mark
    ``t.hi`` and the run after it, if any, ends after that mark: the
    harness syncs the device before the mark, so the window's runs have
    ended by it and the next one starts after it, each by a whole run's
    length, far more than the clocks' alignment can shift it."""
    program = PROGRAMS[kind]
    ds = sorted((s for s in spans if s.name == "engine.dispatch"
                 and s.t0 >= t.t0 and s.attrs.get("kind") == kind
                 and "jit_" + s.attrs.get("program", program[4:]) == program),
                key=lambda s: s.t0)
    window = [s for s in ds if s.t1 <= t.t1]
    n = len(window)
    if not n <= len(runs) <= len(ds) or (
            n and runs[n - 1][0] >= t.hi) or (
            len(runs) > n and sum(runs[n]) <= t.hi):
        raise RuntimeError(f"{program}: the engine dispatched {n} in the "
                           f"traced window and {len(ds)} since it began, the "
                           f"trace holds {len(runs)} runs of it")
    return [(d, s.attrs[unit]) for (_, d), s in zip(runs, window)]


def _program_time(run, kind: str, unit: str):
    """Device ns of ``kind``'s program per ``unit`` in the traced window,
    or None where nothing ran."""
    spans = engine_spans()
    if spans is None or run.trace is None or run.modules is None:
        return None
    t = run.trace
    runs = program_runs(run.modules, PROGRAMS[kind])
    try:
        pairs = pair_runs(spans, t, runs, kind, unit)
    except RuntimeError:
        _log_edges(runs, t, PROGRAMS[kind])
        raise
    if not pairs:
        return None
    ns, units = sum(p[0] for p in pairs), sum(p[1] for p in pairs)
    print(f"[programs] {PROGRAMS[kind]} runs={len(pairs)} device_s="
          f"{ns / 1e9} {unit}={units}", file=sys.stderr, flush=True)
    return ns / units


def _log_edges(runs, t, program: str):
    """The runs of ``program`` nearest the traced window's two marks, as
    (start, end) in ms from the mark, for reading a count that differs."""
    mine = [(s, s + d) for s, d in runs]
    ms = lambda pairs, at: [(round((a - at) / 1e6, 4), round((b - at) / 1e6, 4))
                            for a, b in pairs]
    first = mine[:3]
    last = [p for p in mine if p[0] < t.hi][-3:] + \
        [p for p in mine if p[0] >= t.hi][:1]
    print(f"[programs] {program} at the start mark {ms(first, t.lo)}, at "
          f"the end mark {ms(last, t.hi)}", file=sys.stderr, flush=True)


def host_ms_mean(run):
    """Mean host time per working step of the traced window outside its
    device waits (ms). Logs the window's host time by span to standard
    error."""
    spans = engine_spans()
    if spans is None or run.trace is None:
        return None
    t0, t1 = run.trace.t0, run.trace.t1
    ms = host_ms(spans, t0, t1)
    if not ms:
        return None
    print(f"[engine spans] steps={len(ms)} by_span="
          f"{json.dumps(host_spans(spans, t0, t1))}", file=sys.stderr,
          flush=True)
    return sum(ms) / len(ms)


def decode_step_ms(run):
    """Device time of the decode chunks run in the traced window over the
    decode steps they ran (ms a step)."""
    ns = _program_time(run, "decode", "n_steps")
    return None if ns is None else ns / 1e6


def prefill_us_per_token(run):
    """Device time of the prefill chunks run in the traced window over
    the prompt tokens they prefilled (us a token)."""
    ns = _program_time(run, "prefill", "tokens")
    return None if ns is None else ns / 1e3
