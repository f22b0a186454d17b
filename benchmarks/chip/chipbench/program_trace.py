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
  events, ``run.modules``, inside the traced window on the trace's
  clock) over a count its dispatch spans carry: decode steps of a
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


def program_runs(modules, lo: float, hi: float, program: str) -> list:
    """Durations (ns) of the runs of ``program`` that start and end inside
    [lo, hi], in the order they started."""
    return [d for n, s, d in sorted(modules, key=lambda e: e[1])
            if n.split("(", 1)[0] == program and s >= lo and s + d <= hi]


def pair_runs(spans, t0: float, t1: float, runs: list, kind: str,
              unit: str) -> list:
    """[(device ns, ``unit``)]: each run paired, in order, with the
    ``engine.dispatch`` span of ``kind`` inside [t0, t1] that enqueued
    it. Runs and spans must agree in number."""
    ds = sorted((s for s in _in(spans, t0, t1, "engine.dispatch")
                 if s.attrs.get("kind") == kind), key=lambda s: s.t0)
    if len(ds) != len(runs):
        raise RuntimeError(f"{PROGRAMS[kind]}: the engine dispatched "
                           f"{len(ds)} in the traced window, the trace holds "
                           f"{len(runs)} runs of it")
    return [(d, s.attrs[unit]) for d, s in zip(runs, ds)]


def _program_time(run, kind: str, unit: str):
    """Device ns of ``kind``'s program per ``unit`` in the traced window,
    or None where nothing ran."""
    spans = engine_spans()
    if spans is None or run.trace is None or run.modules is None:
        return None
    t = run.trace
    runs = program_runs(run.modules, t.lo, t.hi, PROGRAMS[kind])
    pairs = pair_runs(spans, t.t0, t.t1, runs, kind, unit)
    if not pairs:
        return None
    ns, units = sum(p[0] for p in pairs), sum(p[1] for p in pairs)
    print(f"[programs] {PROGRAMS[kind]} runs={len(pairs)} device_s="
          f"{ns / 1e9} {unit}={units}", file=sys.stderr, flush=True)
    return ns / units


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
