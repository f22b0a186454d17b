"""The program's spans in a traced window (``chipbench.program_trace``)
on small synthetic inputs; the existing readers unchanged by the
engine's spans; and, on a tiny CPU run, the engine's own dispatch spans
against the harness's attribution, and ``host_ms`` read from them."""
import contextlib
import io
import json
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

from chipbench import bench, files, program_trace as pt  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from repro.serve import spans as spans_mod  # noqa: E402
from repro.serve.spans import SpanRecorder  # noqa: E402
from test_chipbench_run import BENCH, TINY_CONF, TINY_MIX  # noqa: E402

# the per-layer and end-to-end metrics the benchmark had before the
# engine recorded spans
EXISTING = ["ttft_p95_ms", "tpot_p95_ms", "output_tok_s", "setup_s",
            "queue_wait_p95_ms.chat", "decode_occupancy.batch",
            "step_ms.chat", "step_ms.batch", "mfu.chat", "mfu.batch",
            "glu_2d_roofline.batch", "device_idle.chat", "device_idle.batch",
            "window_compiles.chat", "window_compiles.batch"]


def span(name, t0, t1, parent=None, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, parent=parent,
                                 attrs=attrs)


def step_spans(t0, dec_steps=8, chunks=(), wait=(0.02, 0.05)):
    """One engine step at host time t0 (s) lasting 0.1 s: a decode
    dispatch, prefill chunk dispatches and one decode wait."""
    out = [span("engine.step", t0, t0 + 0.1, step=0, n_slots=4,
                n_steps=dec_steps, n_chunks=len(chunks)),
           span("engine.dispatch", t0 + 0.001, t0 + 0.002, "engine.step",
                kind="decode", program="decode_chunk", n_steps=dec_steps,
                n_slots=4)]
    for i, (uid, tokens) in enumerate(chunks):
        out.append(span("engine.dispatch", t0 + 0.003 + i * 1e-3,
                        t0 + 0.0035 + i * 1e-3, "engine.step", kind="prefill",
                        program="prefill_chunk", uid=uid, slot=0, pos=0,
                        tokens=tokens, bucket=512))
    out.append(span("engine.wait", t0 + wait[0], t0 + wait[1], "engine.step",
                    kind="decode"))
    return out


def test_host_time_outside_waits():
    spans = step_spans(1.0) + step_spans(1.2, wait=(0.01, 0.09))
    spans.append(span("engine.wait", 1.25, 1.26, "engine.step",
                      kind="first_token", uid=3))
    # an idle step, and a step outside the window, count for nothing
    spans += [span("engine.step", 1.4, 1.41, n_slots=0, n_steps=0,
                   n_chunks=0)] + step_spans(5.0)
    got = pt.host_ms(spans, 0.5, 2.0)
    assert got == pytest.approx([70.0, 10.0])


def test_host_time_by_span_is_own_time():
    spans = (step_spans(1.0, 8, [(5, 300), (6, 212)])
             + [span("engine.admit", 1.2, 1.21, "engine.step"),
                span("engine.step", 1.19, 1.25, n_slots=0, n_steps=0,
                     n_chunks=0)])
    got = pt.host_spans(spans, 0.5, 2.0)
    assert got["engine.step"][0] == 2
    # the step's own time: 0.1 s less a decode dispatch (1 ms), two
    # chunk dispatches (0.5 ms each) and the wait (30 ms); then the idle
    # step less its admission
    assert got["engine.step"][1] == pytest.approx(0.1 - 0.002 - 0.03 + 0.05)
    assert got["engine.dispatch:decode"] == [1, pytest.approx(0.001)]
    assert got["engine.dispatch:prefill"] == [2, pytest.approx(0.001)]
    assert got["engine.wait:decode"] == [1, pytest.approx(0.03)]
    assert got["engine.admit"] == [1, pytest.approx(0.01)]
    assert pt.host_spans(spans, 3.0, 4.0) == {}


def _recorded(spans_):
    """A recorder holding ``spans_``: the process's newest, where the
    readers look."""
    rec = SpanRecorder()
    rec.spans.extend(spans_)
    return rec


def _traced(t0, t1):
    return types.SimpleNamespace(trace=types.SimpleNamespace(t0=t0, t1=t1))


def test_host_ms_reads_the_newest_recorder():
    _recorded(step_spans(10.0) + step_spans(10.2, wait=(0.01, 0.09)))
    assert spans_mod.latest().spans[0].name == "engine.step"
    for name in ("host_ms.chat", "host_ms.batch"):
        got = files.metric_reader(name)(_traced(9.9, 10.4))
        assert got == pytest.approx((70.0 + 20.0) / 2)


def test_metrics_without_the_program_spans_are_none(monkeypatch):
    _recorded(step_spans(10.0))
    # no traced window, or no working step inside it
    assert pt.host_ms_mean(types.SimpleNamespace(trace=None)) is None
    assert pt.host_ms_mean(_traced(20.0, 21.0)) is None
    assert pt.host_ms_mean(_traced(9.9, 10.4)) == pytest.approx(70.0)
    # a program that predates the recorder
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert pt.engine_spans() is None
    assert pt.host_ms_mean(_traced(9.9, 10.4)) is None


def _synthetic_run():
    """A recorded backlog-like run: requests, steps with GLU calls, and a
    traced window whose ops hold the kernel's events."""
    peaks = peaks_for("TPU v5 lite")
    glu = ('%branch_0_fun.8 = bf16[512,8192]{1,0:T(8,128)(2,1)} custom-call('
           '%x, %a, %b, %c), custom_call_target="tpu_custom_call"')
    ops = [(glu, 1e6 + i * 2e6, 1.5e6) for i in range(4)]
    ops += [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 9e6, 3e5),
            ("%copy.2 = bf16[8]{0} copy(%p)", 9.5e6, 1e5)]
    spans = [("bench.trace_start", 0.0, 1e3), ("bench.step", 5e5, 4e6),
             ("bench.step", 5.2e6, 5e6), ("bench.window_end", 1.2e7, 1e3)]
    trace = bench.TraceData(ops=ops, spans=spans, lo=0.0, hi=1.2e7,
                            t0=10.0, t1=10.012)
    recs = []
    for i in range(6):
        r = bench.Rec(uid=i, prompt_len=1000, max_new=20, due=1.0 + i,
                      submitted=1.0 + i, prompt=None, admitted=1.2 + i,
                      first=1.5 + i, last=3.0 + i, tokens=[1] * 20, done=True)
        recs.append(r)
    steps = [bench.Step(t0=10.001, t1=10.005, flops=3e12,
                        glu_rows=[(512, 4)]),
             bench.Step(t0=10.006, t1=10.011, flops=2e12, glu_rows=[])]
    return bench.Run(
        cell={"name": "x"}, conf={"hidden_size": 2048,
                                  "intermediate_size": 8192},
        mix={"kind": "open_loop"}, peaks=peaks, slots=32, setup_s=50.0,
        window=(2.0, 12.0), due_window=(2.0, 6.0), records=recs,
        steps=steps, tokens_in_window=600,
        counters={"decode_tokens": 500, "decode_steps": 20,
                  "prefill_tokens": 4000, "prefill_chunks": 8,
                  "prefill_requests": 4},
        window_compiles=0, trace=trace)


def _with_engine_spans(run):
    """The same run as a program that records spans gives it: engine
    spans on the trace's clock inside the harness's, and the engine's
    recorder holding the window's steps."""
    import copy
    r = copy.deepcopy(run)
    r.trace.spans = r.trace.spans + [
        ("engine.step", 5.5e5, 3.9e6), ("engine.admit", 5.6e5, 1e4),
        ("engine.dispatch", 6e5, 2e4), ("engine.wait", 7e5, 3.6e6),
        ("engine.harvest", 4.35e6, 5e4), ("engine.step", 5.3e6, 4.8e6),
        ("engine.wait", 5.4e6, 4.5e6)]
    _recorded([span("engine.step", 10.001, 10.005, n_slots=4, n_steps=8,
                     n_chunks=1),
               span("engine.wait", 10.002, 10.004, "engine.step",
                    kind="decode")])
    return r


def test_existing_readers_unchanged_by_engine_spans():
    """Every accepted metric reads the same on one recorded run with and
    without the engine's spans in the trace and in its recorder."""
    base = _synthetic_run()
    spanned = _with_engine_spans(base)
    assert pt.host_ms_mean(spanned) == pytest.approx(2.0)
    read = {}
    for name in EXISTING:
        f = files.metric_reader(name)
        read[name] = f(base)
        assert f(spanned) == read[name], name
    assert read["glu_2d_roofline.batch"] is not None
    assert read["device_idle.chat"] is not None
    assert read["mfu.chat"] is not None
    bench_json = files.load_benchmark()
    have = [m["name"] for m in bench_json["end_to_end"] + bench_json[
        "per_layer"]]
    assert set(EXISTING) <= set(have)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("chipbench_spans")
    for d in ("configs", "traffic"):
        (r / d).mkdir()
    (r / "configs" / "tiny.json").write_text(json.dumps(TINY_CONF))
    (r / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    for d in ("metrics", "reference"):
        (r / d).symlink_to(CHIP / d)
    return r


@pytest.fixture(scope="module")
def tiny(root):
    """One tiny traceless CPU run: its engine, the harness's driver, and
    the process's newest recorder when the run has ended."""
    drivers, engines = [], []

    class Capture(bench.Driver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            drivers.append(self)

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setattr(bench, "Driver", Capture)
        rc = bench.main(["--workload", "tiny.chat", "--seed", str(2**31 + 5),
                         "--seconds", "1.5", "--trace", "0"], root=root,
                        require_tpu=False, bench=BENCH,
                        program_hook=engines.append, compile_cache=False)
    assert rc == 0
    assert json.loads(out.getvalue().strip().splitlines()[-1])["correct"]
    [engine], [driver] = engines, drivers
    return engine, driver, spans_mod.latest()


def test_engine_dispatches_agree_with_the_harness(tiny):
    """The prefill chunks and fused-GLU calls the harness attributes to
    each step from the engine's counters (``Driver._account``, a copy of
    the scheduler's policy) are the ones the engine's dispatch spans
    record: the day the scheduler changes, this fails."""
    engine, driver, _ = tiny
    L = TINY_CONF["num_hidden_layers"]
    spans = list(engine.spans)
    n_chunks = 0
    by_uid: dict = {}
    for s in driver.steps:
        inside = [x for x in spans if x.name == "engine.dispatch"
                  and s.t0 <= x.t0 and x.t1 <= s.t1]
        want = [(driver.slots, x.attrs["n_steps"] * L) for x in inside
                if x.attrs["kind"] == "decode"]
        want += [(x.attrs["bucket"], L) for x in inside
                 if x.attrs["kind"] == "prefill"]
        assert sorted(s.glu_rows) == sorted(want)
        for x in inside:
            if x.attrs["kind"] == "prefill":
                n_chunks += 1
                by_uid[x.attrs["uid"]] = by_uid.get(x.attrs["uid"], 0) + \
                    x.attrs["tokens"]
    assert n_chunks > 0
    prefilled = {r.uid: r.prefilled for r in driver.recs.values()
                 if r.prefilled}
    assert by_uid == prefilled


def test_host_ms_reads_the_engine_of_a_run(tiny, monkeypatch):
    """After the run the reader finds the engine's spans, and reads the
    mean host time of the harness's steps less the engine's waits."""
    engine, driver, latest = tiny
    assert latest is engine._rec
    monkeypatch.setattr(spans_mod, "_latest", latest)
    steps = driver.steps
    t0, t1 = steps[0].t0, steps[-1].t1
    waits = [x for x in engine.spans if x.name == "engine.wait"
             and t0 <= x.t0 and x.t1 <= t1]
    assert waits
    got = pt.host_ms_mean(_traced(t0, t1))
    want = pt.host_ms(list(engine.spans), t0, t1)
    assert len(want) == len(steps)
    assert got == pytest.approx(sum(want) / len(want))
    assert 0 < got < 1e3 * max(s.t1 - s.t0 for s in steps)
