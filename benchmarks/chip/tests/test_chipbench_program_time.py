"""Per-program device time: the trace's ``XLA Modules`` events kept in the
run, and ``decode_step_ms.*`` / ``prefill_us_per_token.*`` read from them
and the engine's dispatch spans, on a synthetic trace."""
import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

from chipbench import bench, files, program_trace as pt, trace as tr  # noqa: E402
from repro.serve.spans import SpanRecorder  # noqa: E402
from test_chipbench_program_trace import span  # noqa: E402

DECODE = "jit_decode_chunk(13764310839150306731)"
PREFILL = "jit_prefill_chunk(2934151023395622011)"


def _window():
    """A traced window [0, 1e9) ns on the trace's clock and [10, 11) s on
    the host's: two engine steps, each a decode chunk (8, then 3 steps)
    and prefill chunks of 512 and 300 tokens, and the program runs that
    they enqueued; runs of other programs, and a run and a span outside
    the window, that count for nothing."""
    spans = []
    for k, (t, n_steps, chunks) in enumerate([(10.1, 8, [512, 300]),
                                              (10.5, 3, [512])]):
        spans.append(span("engine.step", t, t + 0.3, n_slots=4,
                          n_steps=n_steps, n_chunks=len(chunks)))
        spans.append(span("engine.dispatch", t + 0.01, t + 0.02,
                          "engine.step", kind="decode", n_steps=n_steps,
                          n_slots=4))
        for i, c in enumerate(chunks):
            spans.append(span("engine.dispatch", t + 0.03 + i * 0.01,
                              t + 0.035 + i * 0.01, "engine.step",
                              kind="prefill", uid=k, slot=0, pos=0, tokens=c,
                              bucket=512))
    spans.append(span("engine.dispatch", 11.5, 11.6, kind="decode",
                      n_steps=8))
    ms = 1e6
    modules = [(DECODE, 110 * ms, 40 * ms), (PREFILL, 150 * ms, 51.2 * ms),
               (PREFILL, 202 * ms, 30 * ms), (DECODE, 510 * ms, 15 * ms),
               (PREFILL, 530 * ms, 51.2 * ms),
               ("jit_convert_element_type(7)", 600 * ms, 1e3),
               (DECODE, 1.2e9, 40 * ms)]
    trace = types.SimpleNamespace(lo=0.0, hi=1e9, t0=10.0, t1=11.0)
    return spans, modules, trace


def _run(modules, trace):
    return types.SimpleNamespace(trace=trace, modules=modules)


def test_program_runs_in_the_window():
    _, modules, trace = _window()
    assert pt.program_runs(modules, trace.lo, trace.hi, "jit_decode_chunk") \
        == [40e6, 15e6]
    assert len(pt.program_runs(modules, 0, 1e9, "jit_prefill_chunk")) == 3
    assert pt.program_runs(modules, 0, 1e9, "jit_decode") == []


def test_readers_divide_device_time_by_the_spans_counts():
    spans, modules, trace = _window()
    SpanRecorder().spans.extend(spans)
    run = _run(modules, trace)
    for cell in ("chat", "batch"):
        # 55 ms of decode chunks over 8 + 3 steps
        assert files.metric_reader(f"decode_step_ms.{cell}")(run) == \
            pytest.approx(55.0 / 11)
        # 132.4 ms of prefill chunks over 1324 tokens
        assert files.metric_reader(f"prefill_us_per_token.{cell}")(run) == \
            pytest.approx(132.4e3 / 1324)


def test_a_count_mismatch_raises():
    spans, modules, trace = _window()
    SpanRecorder().spans.extend(spans)
    with pytest.raises(RuntimeError, match="dispatched 3 in the traced "
                       "window, the trace holds 2 runs"):
        pt.prefill_us_per_token(_run(modules[:2] + modules[3:], trace))
    with pytest.raises(RuntimeError, match="jit_decode_chunk"):
        pt.decode_step_ms(_run(modules[1:], trace))


def test_nothing_to_read_is_none(monkeypatch):
    spans, modules, trace = _window()
    SpanRecorder().spans.extend(spans)
    assert pt.decode_step_ms(_run(modules, None)) is None
    assert pt.decode_step_ms(_run(None, trace)) is None
    # a window in which the engine ran no such program
    quiet = types.SimpleNamespace(lo=2e9, hi=3e9, t0=20.0, t1=21.0)
    assert pt.prefill_us_per_token(_run(modules, quiet)) is None
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert pt.decode_step_ms(_run(modules, trace)) is None


class _Plane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = [types.SimpleNamespace(
            name=ln, events=[types.SimpleNamespace(
                name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
            for ln, evs in lines.items()]


def test_read_trace_keeps_the_first_chips_program_runs(tmp_path, monkeypatch):
    """``read_xplane`` collects each device plane's ``XLA Modules`` line
    beside its ops; ``read_trace`` hands the first chip's to the run."""
    import jax.profiler
    (tmp_path / "a.xplane.pb").write_bytes(b"")
    planes = [
        _Plane("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(%p)", 5.0, 2.0)],
            "XLA Modules": [(DECODE, 4.0, 4.0)],
            "Steps": [("0", 0.0, 9.0)]}),
        _Plane("/device:TPU:1", {"XLA Modules": [(PREFILL, 4.0, 1.0)]}),
        _Plane("/host:CPU", {"python": [
            ("bench.trace_start", 1.0, 0.1), ("bench.window_end", 9.0, 0.1),
            ("engine.step", 2.0, 3.0)]})]
    monkeypatch.setattr(jax.profiler, "ProfileData", types.SimpleNamespace(
        from_file=lambda path: types.SimpleNamespace(planes=planes)))
    ops, spans, modules = tr.read_xplane(str(tmp_path))
    assert modules == {"/device:TPU:0": [(DECODE, 4.0, 4.0)],
                       "/device:TPU:1": [(PREFILL, 4.0, 1.0)]}
    assert ops["/device:TPU:1"] == []
    assert [s[0] for s in spans] == ["bench.trace_start", "bench.window_end"]
    trace, runs = bench.read_trace(str(tmp_path), 10.0, 11.0)
    assert runs == [(DECODE, 4.0, 4.0)]
    assert (trace.lo, trace.hi, len(trace.ops)) == (1.0, 9.0, 1)
