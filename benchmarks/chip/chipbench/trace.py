"""Reduction of a profiler trace to device busy time, idle gaps, kernel
time and the top device operations.

The functions on plain lists of ``(name, start_ns, duration_ns)`` do the
arithmetic and are what the tests check; ``read_xplane`` turns the
profiler's ``.xplane.pb`` into such lists.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def read_xplane(trace_dir: str):
    """(device ops per chip {plane: [(name, start_ns, dur_ns)]},
    host spans [(name, start_ns, dur_ns)] whose name starts with
    ``SPAN_PREFIX``, program runs per chip {plane: [(name, start_ns,
    dur_ns)]}: one event per run of a compiled program, named
    ``jit_<function>(<fingerprint>)``)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, spans, modules = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
            ops[plane.name] = lines[OPS_LINE]
            modules[plane.name] = lines[MODULES_LINE]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return ops, spans, modules


def merged(events, lo: float, hi: float):
    """Union of the events' intervals clipped to [lo, hi], as sorted,
    disjoint (start, end) pairs."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s < hi and s + d > lo)
    out = []
    for s, e in iv:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def idle_gaps(events, lo: float, hi: float):
    """The intervals of [lo, hi] in which no event runs."""
    gaps, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def label_gaps(gaps, spans, top: int = 10):
    """Idle time by what the host was doing: each gap is split over the
    host spans that cover it (innermost, i.e. shortest, span wins), the
    rest is "host:outside spans". Returns [[label, seconds], ...] with
    the count of gaps in the label, longest total first."""
    spans = sorted(spans, key=lambda s: s[2])          # innermost first
    total = collections.defaultdict(float)
    count = collections.defaultdict(int)
    for g0, g1 in gaps:
        pieces = [(g0, g1)]
        for name, s, d in spans:
            nxt = []
            for p0, p1 in pieces:
                a, b = max(p0, s), min(p1, s + d)
                if a < b:
                    total[name] += b - a
                    count[name] += 1
                    if p0 < a:
                        nxt.append((p0, a))
                    if b < p1:
                        nxt.append((b, p1))
                else:
                    nxt.append((p0, p1))
            pieces = nxt
            if not pieces:
                break
        for p0, p1 in pieces:
            total["host:outside spans"] += p1 - p0
            count["host:outside spans"] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[f"{name} (n={count[name]})", ns / 1e9] for name, ns in rows]


_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-_]*)\(")


def hlo_parts(name: str):
    """(instruction, result type, opcode) of a device event named by its
    HLO instruction text (``%fusion.3 = bf16[8,128]{...} fusion(...)``);
    a name that is not such text is its own instruction."""
    if " = " not in name:
        return name, "", ""
    inst, rest = name.split(" = ", 1)
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    rtype = "tuple" if rest.startswith("(") else rest.split("{", 1)[0]
    return inst.strip().lstrip("%"), rtype.strip(), opcode


def short_name(name: str) -> str:
    inst, rtype, opcode = hlo_parts(name)
    return " ".join(x for x in (inst, rtype, opcode) if x)


def self_pieces(events):
    """The time each event runs outside the events nested in it, as
    [(name, start, end)] pieces. An event nested in another (a fusion in
    a loop's body) takes its time from the outer one; events that only
    overlap each keep their own."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []                  # stack: [name, start, end, cursor]

    def close(top):
        if top[3] < top[2]:
            out.append((top[0], top[3], top[2]))

    for name, s, d in evs:
        e = s + d
        while stack and not (stack[-1][1] <= s and e <= stack[-1][2]):
            close(stack.pop())
        if stack:
            top = stack[-1]
            if s > top[3]:
                out.append((top[0], top[3], s))
            top[3] = max(top[3], e)
        stack.append([name, s, e, s])
    while stack:
        close(stack.pop())
    return out


def op_totals(events, lo: float, hi: float, top: int = 10):
    """Device seconds per operation inside [lo, hi], each operation's own
    time without the operations nested in it, largest first. Names are
    shortened to instruction, result type and opcode."""
    total = collections.defaultdict(float)
    for name, s, e in self_pieces(events):
        a, b = max(s, lo), min(e, hi)
        if a < b:
            total[short_name(name)] += b - a
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in rows]


def kernel_time(events, lo: float, hi: float, match) -> tuple[float, int]:
    """(seconds, count) of the events inside [lo, hi] whose name
    satisfies ``match``. An event must start and end inside the window."""
    secs, n = 0.0, 0
    for name, s, d in events:
        if s >= lo and s + d <= hi and match(name):
            secs += d / 1e9
            n += 1
    return secs, n
