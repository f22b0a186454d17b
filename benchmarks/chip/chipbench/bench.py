"""One run of one cell: set-up, a measured window of the cell's traffic,
the drain, the correctness check, and the result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from the same window with
the profiler on for its last seconds. The last line of standard output
is one JSON object; the last lines of standard error are the numbers
compared with their limits.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from . import check, files, flops, trace as tr
from .peaks import peaks_for
from .stats import due_in
from .traffic import Traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Backend compiles of this process, with when each ended, and every
    other timed event of JAX's compile path (tracing, lowering, reading
    the persistent cache), which the window should not hold either."""

    def __init__(self, jax):
        self.ends: list[tuple[float, float]] = []
        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        self.events.append((time.perf_counter(), event, duration))
        if event == COMPILE_EVENT:
            self.ends.append((time.perf_counter(), duration))

    def count(self, start: float, end: float) -> int:
        return sum(1 for t, _ in self.ends if start <= t < end)

    def others(self, start: float, end: float) -> dict:
        """{event: [count, seconds]} of the timed events in [start, end)."""
        out: dict = {}
        for t, name, d in self.events:
            if start <= t < end:
                c = out.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += d
        return out


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it (host clock)."""
    uid: int
    prompt_len: int
    max_new: int
    due: float
    submitted: float
    prompt: np.ndarray
    admitted: float | None = None
    first: float | None = None
    last: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    prefilled: int = 0             # prompt tokens the harness attributes


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    flops: float
    glu_rows: list                 # [(rows M, calls)] fused-GLU calls


@dataclasses.dataclass
class TraceData:
    ops: list                      # device ops [(name, start_ns, dur_ns)]
    spans: list                    # host spans [(name, start_ns, dur_ns)]
    lo: float                      # traced window on the trace clock (ns)
    hi: float
    t0: float                      # the same window on the host clock (s)
    t1: float


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    conf: dict
    mix: dict
    peaks: object
    slots: int
    setup_s: float
    window: tuple                  # (start, end), host clock
    due_window: tuple              # (start, end) of the due times counted
    records: list
    steps: list
    tokens_in_window: int
    counters: dict                 # engine counters over the window
    window_compiles: int
    trace: TraceData | None = None
    modules: list | None = None    # the traced window's program runs on
                                   # the first chip [(name, start_ns,
                                   # dur_ns)] ("XLA Modules")

    def due_in_window(self):
        return due_in(self.records, *self.due_window)

    def steps_in_window(self):
        a, b = self.window
        return [s for s in self.steps if s.t0 >= a and s.t1 <= b]


def _use_cache(jax):
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), holding every program,
    however small or quick to compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(files.REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Driver:
    """Feeds the engine its traffic and keeps the per-request and
    per-step records."""

    def __init__(self, engine, traffic: Traffic, conf: dict, mix: dict,
                 spans: bool = False):
        import jax
        self.jax = jax
        self.engine, self.traffic, self.conf, self.mix = engine, traffic, conf, mix
        self.spans = spans
        self.recs: dict[int, Rec] = {}
        self.steps: list[Step] = []
        self.completed_tokens = 0
        self.n_seen = 0
        self.lateness: list[float] = []
        self.next_req = None
        self.start = None
        self.slots = engine.ecfg.slots
        self.cp = engine.ecfg.chunk_prefill
        self.L = conf["num_hidden_layers"]
        self.depth = int(mix.get("queue_depth", self.slots))

    def span(self, name):
        if self.spans:
            return self.jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    # -- traffic ---------------------------------------------------------

    def _submit(self, req, due: float):
        now = time.perf_counter()
        uid = self.engine.submit(req.tokens, req.max_new, arrival_s=due)
        self.recs[uid] = Rec(uid=uid, prompt_len=req.prompt_len,
                             max_new=req.max_new, due=due, submitted=now,
                             prompt=req.tokens)
        self.lateness.append(now - due)

    def submit_due(self, now: float):
        """Open loop: send every request due by ``now``. Backlog: top the
        queue up to its depth."""
        with self.span("submit"):
            if self.traffic.kind == "open_loop":
                while True:
                    if self.next_req is None:
                        self.next_req = self.traffic.next()
                    due = self.start + self.next_req.due
                    if due > now:
                        return due
                    self._submit(self.next_req, due)
                    self.next_req = None
            while len(self.engine.sched.queue) < self.depth:
                self._submit(self.traffic.next(), time.perf_counter())
            return None

    # -- one engine step, with its accounting ----------------------------

    def inflight_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.engine.sched.slots
                   if r is not None)

    def tokens_total(self) -> int:
        return self.completed_tokens + self.inflight_tokens()

    def step(self) -> bool:
        eng = self.engine
        before = {r.request.uid: len(r.tokens) for r in eng.sched.slots
                  if r is not None}
        s0 = (eng.stats.prefill_tokens, eng.stats.decode_steps)
        t0 = time.perf_counter()
        with self.span("step"):
            did = eng.step()
        t1 = time.perf_counter()
        after = {}
        for r in eng.sched.slots:
            if r is not None:
                after[r.request.uid] = r
                rec = self.recs.get(r.request.uid)
                if rec is not None:
                    if rec.admitted is None:
                        rec.admitted = r.admitted_at
                    if rec.first is None and r.token_times:
                        rec.first = r.token_times[0]
        new = eng.completions[self.n_seen:]
        self.n_seen = len(eng.completions)
        for c in new:
            rec = self.recs.get(c.uid)
            self.completed_tokens += len(c.tokens)
            if rec is None:
                continue
            rec.admitted = c.admitted_at
            rec.first = c.arrival_s + c.ttft_s
            rec.last = c.finished_at
            rec.tokens = [int(t) for t in c.tokens]
            rec.done = True
        if did:
            self._account(before, after, {c.uid: c for c in new}, s0, t0, t1)
        return did

    def _account(self, before, after, done, s0, t0, t1):
        """FLOPs and fused-GLU calls of one step, from the engine's
        public counters: decode tokens per request from its token count,
        prefill tokens handed out in uid order, at most one chunk of
        ``chunk_prefill`` per request mid-prompt (the token-budget plan)."""
        eng, conf = self.engine, self.conf
        d_pf = eng.stats.prefill_tokens - s0[0]
        d_steps = eng.stats.decode_steps - s0[1]
        uids = sorted(set(after) | set(done))
        total = 0.0
        glu = []
        if d_steps:
            glu.append((self.slots, d_steps * self.L))
        left = d_pf
        for uid in uids:
            rec = self.recs.get(uid)
            n_after = (len(after[uid].tokens) if uid in after
                       else len(done[uid].tokens))
            n_before = before.get(uid, 0)
            if rec is None:
                continue
            if n_before == 0:
                c = min(self.cp, rec.prompt_len - rec.prefilled, left)
                if c > 0:
                    total += flops.span_flops(conf, rec.prefilled, c)
                    glu.append((self._bucket(c), self.L))
                    rec.prefilled += c
                    left -= c
                if n_after > 0:
                    total += flops.head_flops(conf)       # first token
                n_before = 1 if n_after > 0 else 0
            emitted = n_after - n_before
            if emitted > 0:
                total += flops.span_flops(conf, rec.prompt_len + n_before - 1,
                                          emitted)
                total += emitted * flops.head_flops(conf)
        self.steps.append(Step(t0, t1, total, glu))

    def _bucket(self, c: int) -> int:
        from . import program
        return program.chunk_bucket(c, self.cp)


def _sync(jax, engine):
    jax.block_until_ready((engine.cache, engine.state))


def serve(driver: Driver, seconds: float, trace_s: float, trace_dir):
    """Warm-up phase, window and drain. Returns a dict of the window's
    bounds, tokens and counters, and the trace's bounds. In an open loop
    the warm-up is whole cycles of the traffic, so the window starts on a
    cycle's boundary, and the requests counted are those due in the
    nominal window ``[w0, w1)``, however late a step starts it."""
    jax, eng, mix = driver.jax, driver.engine, driver.mix
    open_loop = driver.traffic.kind == "open_loop"
    warmup_s = float(mix["warmup_s"])
    if open_loop:
        span = driver.traffic.span
        warmup_s = span * math.ceil(warmup_s / span - 1e-9)
    drain_cap = float(mix.get("drain_cap_s", 60))
    driver.start = time.perf_counter()
    w0, w1 = driver.start + warmup_s, driver.start + warmup_s + seconds
    out = {"due_window": (w0, w1)}
    tracing = False
    while True:
        now = time.perf_counter()
        if "a" not in out and now >= w0:
            out.update(a=now, tok_a=driver.tokens_total(),
                       stats_a=dataclasses.replace(eng.stats))
        if "a" in out and "b" not in out and now >= w1:
            if tracing:
                _sync(jax, eng)
                with driver.span("window_end"):
                    pass
                out["trace_t1"] = time.perf_counter()
                now = out["trace_t1"]
            out.update(b=now, tok_b=driver.tokens_total(),
                       stats_b=dataclasses.replace(eng.stats))
        if "b" in out:
            if not open_loop:
                break
            pending = [r for r in driver.recs.values()
                       if w0 <= r.due < w1 and not r.done]
            if not pending or now > out["b"] + drain_cap:
                break
        if (trace_dir is not None and not tracing and "b" not in out
                and now >= w1 - trace_s):
            _sync(jax, eng)
            jax.profiler.start_trace(trace_dir)
            tracing = True
            with driver.span("trace_start"):
                pass
            out["trace_t0"] = time.perf_counter()
        next_due = driver.submit_due(now)
        if not driver.step():
            if open_loop and next_due is not None:
                wake = min(next_due, w0 if "a" not in out else
                           (w1 if "b" not in out else next_due))
                with driver.span("wait"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
    if tracing:
        _sync(jax, eng)
        jax.profiler.stop_trace()
    return out


def counters(a, b) -> dict:
    keys = ("decode_tokens", "decode_steps", "prefill_tokens",
            "prefill_chunks", "prefill_requests")
    return {k: getattr(b, k) - getattr(a, k) for k in keys}


def read_trace(trace_dir, t0: float, t1: float):
    """(TraceData of the first chip, that chip's program runs)."""
    ops_by_chip, spans, modules = tr.read_xplane(trace_dir)
    if not ops_by_chip:
        raise RuntimeError("the trace holds no device plane")
    marks = {n: s for n, s, _ in spans}
    lo = marks.get("bench.trace_start")
    hi = marks.get("bench.window_end")
    if lo is None or hi is None:
        raise RuntimeError("the trace lacks the window's marks")
    chip = next(iter(ops_by_chip))
    return (TraceData(ops=ops_by_chip[chip], spans=spans, lo=lo, hi=hi,
                      t0=t0, t1=t1), modules[chip])


def parse_args(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None, *, t_process: float | None = None, root=files.ROOT,
         repo=files.REPO, require_tpu: bool = True, bench: dict | None = None,
         program_hook=None, compile_cache: bool = True) -> int:
    """``require_tpu=False``, ``program_hook`` and ``compile_cache=False``
    exist for the tests only: they drive a run on the CPU at a tiny size,
    with the timed path broken underneath by the hook
    (``program_hook(engine)``), and leave JAX's settings alone."""
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse_args(argv)
    bench = files.load_benchmark(repo) if bench is None else bench
    cell = files.workload(bench, args.workload)
    conf = files.config_of(cell["config"], root)
    mix = files.traffic_of(cell["traffic"], root)

    import jax
    cache_dir = _use_cache(jax) if compile_cache else None
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"run: needs a TPU, found {platform}; no result")
        return 2
    if len(devices) < int(cell["chips"]):
        log(f"run: the cell needs {cell['chips']} chips, found {len(devices)}")
        return 2
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None
    watch = CompileWatch(jax)
    log(f"[device] {platform} {kind} x{len(devices)} jax={jax.__version__} "
        f"cache={cache_dir}")

    from . import program
    cfg = program.model_config(conf)
    params = program.make_params(cfg, args.seed)
    engine = program.make_engine(cfg, params, mix["engine"])
    del params
    warm = program.warm_up(engine, conf["vocab_size"], args.seed)
    if program_hook is not None:
        program_hook(engine)
    log(f"[setup] warm-up {warm} at {time.perf_counter() - t_process:.3f}s")

    traffic = Traffic(mix, conf["vocab_size"], args.seed)
    trace_s = min(float(mix.get("trace_s", 3.0)), args.seconds / 2)
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-") \
        if args.trace else None
    driver = Driver(engine, traffic, conf, mix, spans=bool(args.trace))
    out = serve(driver, args.seconds, trace_s,
                tmp.name if tmp is not None else None)
    setup_s = out["a"] - t_process
    lat = sorted(driver.lateness)
    log(f"[generator] submitted={len(lat)} late_p50_ms="
        f"{1e3 * lat[len(lat) // 2] if lat else 0} late_max_ms="
        f"{1e3 * lat[-1] if lat else 0}")
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0)

    run = Run(cell=cell, conf=conf, mix=mix, peaks=peaks,
              slots=engine.ecfg.slots, setup_s=setup_s,
              window=(out["a"], out["b"]), due_window=out["due_window"],
              records=sorted(driver.recs.values(), key=lambda r: r.uid),
              steps=driver.steps,
              tokens_in_window=out["tok_b"] - out["tok_a"],
              counters=counters(out["stats_a"], out["stats_b"]),
              window_compiles=watch.count(out["a"], out["b"]))
    if tmp is not None:
        run.trace, run.modules = read_trace(tmp.name, out["trace_t0"],
                                            out["trace_t1"])
        tmp.cleanup()
    log(f"[window] {run.window[1] - run.window[0]:.3f}s tokens="
        f"{run.tokens_in_window} counters={run.counters} "
        f"compiles={run.window_compiles} steps={len(run.steps_in_window())} "
        f"jax_events={watch.others(*run.window)}")
    _log_requests(run)

    # free the program's state before the reference runs
    finished = [r for r in run.records if r.done]
    del engine, driver
    verdict, numbers, _ = judge(conf, mix, finished, args.seed, root)

    breakdown = None
    if run.trace is not None:
        t = run.trace
        breakdown = {
            "device_ops": tr.op_totals(t.ops, t.lo, t.hi),
            "idle_gaps": tr.label_gaps(tr.idle_gaps(t.ops, t.lo, t.hi),
                                       t.spans)}
        log(f"[trace] {json.dumps(breakdown)}")

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in files.metrics_for(bench, cell["name"], section):
        value = files.metric_reader(m["name"], root)(run)
        if value is None:
            continue
        if not math.isfinite(value):
            log(f"run: metric {m['name']} is {value}")
            return 1
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = _attempts(run)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    result = {"correct": verdict, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        busy = tr.busy_ns(run.trace.ops, run.trace.lo, run.trace.hi) / 1e9
        device.update(busy_s=busy,
                      window_s=(run.trace.hi - run.trace.lo) / 1e9)
        result["breakdown"] = breakdown
    result["check"] = numbers
    for name, v in numbers.items():
        log(f"[check] {name} value={v['value']} limit={v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def _log_requests(run: Run):
    """Per-request times of the requests due in the window, and the
    longest host steps, for reading a run that lies far from the rest."""
    due = run.due_in_window()
    if not due:
        return
    ms = lambda x: round(1e3 * x, 1)
    ttft = sorted(ms(r.first - r.due) for r in due if r.first is not None)
    tpot = sorted(ms((r.last - r.first) / (len(r.tokens) - 1)) for r in due
                  if r.done and len(r.tokens) > 1)
    late = sorted(ms(r.submitted - r.due) for r in due)
    steps = sorted(ms(s.t1 - s.t0) for s in run.steps_in_window())
    log(f"[requests] ttft_ms={ttft} tpot_ms={tpot} late_ms={late[-5:]} "
        f"longest_steps_ms={steps[-5:]}")


def _attempts(run: Run) -> tuple[int, int]:
    """Open loop: requests due in the window, and those of them not
    finished by the end of the drain. Backlog: requests finished inside
    the window (none can fail without an error)."""
    if run.mix["kind"] == "open_loop":
        due = run.due_in_window()
        return len(due), sum(1 for r in due if not r.done)
    a, b = run.window
    return sum(1 for r in run.records if r.done and a <= r.last <= b), 0


def judge(conf: dict, mix: dict, finished: list, seed: int, root,
          control: bool = False):
    """(correct, {name: {"value", "limit"}}, rows) from the reference.

    With ``control`` the tokens compared are those that the float8
    control ranks first at the served positions, in the program's place:
    the same comparison has to find them not correct."""
    import gc
    import jax
    gc.collect()
    from . import program, weights
    ref = check.reference_module(conf, root)
    cfg = program.model_config(conf)
    w = weights.fill(program.param_shapes(cfg), seed)
    w = jax.tree.map(lambda a: a.astype(np.float32), w)
    ref.check_shapes(w, conf)
    reqs = check.sample(finished, seed, int(mix["check"]["requests"]))
    rows = check.gaps(ref, w, conf, reqs, control=control,
                      max_seq=mix["engine"]["max_len"])
    del w
    limit = float(conf["check"]["max_logit_gap"])
    key = "control_gap" if control else "gap"
    gap = max((r[key] for r in rows), default=math.inf)
    n_tok = sum(r["tokens"] for r in rows)
    log(f"[check] sampled {len(rows)} requests, {n_tok} served tokens: "
        f"{rows}")
    numbers = {"max_logit_gap": {"value": gap, "limit": limit}}
    ok = bool(rows) and math.isfinite(gap) and gap <= limit
    return ok, numbers, rows
