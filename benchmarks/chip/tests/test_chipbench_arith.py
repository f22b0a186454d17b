"""Trace reduction, percentile/TPOT/window arithmetic and the roofline
share, on small synthetic inputs (no accelerator, no trace file)."""
import math
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import flops, readers, stats, trace as tr  # noqa: E402
from chipbench.peaks import PEAKS, peaks_for  # noqa: E402

# ops on one chip, ns: [0,10) [5,20) [30,40) [50,55) ; window [0, 60)
OPS = [("fusion.1", 0, 10), ("branch_0_fun.3", 5, 15), ("fusion.1", 30, 10),
       ("branch_0_fun.3", 50, 5)]


def test_merged_intervals_union():
    assert tr.merged(OPS, 0, 60) == [(0, 20), (30, 40), (50, 55)]
    assert tr.busy_ns(OPS, 0, 60) == 35
    # clipping to the window
    assert tr.busy_ns(OPS, 8, 52) == 12 + 10 + 2


def test_idle_gaps_and_labels():
    gaps = tr.idle_gaps(OPS, 0, 60)
    assert gaps == [(20, 30), (40, 50), (55, 60)]
    spans = [("bench.step", 18, 14), ("bench.submit", 42, 4),
             ("bench.wait", 0, 60)]
    rows = dict(tr.label_gaps(gaps, spans))
    # the innermost span covering each piece wins; the outer wait span
    # takes what the step and submit spans leave
    assert rows["bench.step (n=1)"] == pytest.approx(10 / 1e9)
    assert rows["bench.submit (n=1)"] == pytest.approx(4 / 1e9)
    assert rows["bench.wait (n=3)"] == pytest.approx(11 / 1e9)
    rows = dict(tr.label_gaps(gaps, []))
    assert rows["host:outside spans (n=3)"] == pytest.approx(25 / 1e9)


def test_op_totals_and_kernel_time():
    top = tr.op_totals(OPS + [("copy.1", 56, 2)], 0, 60)
    assert dict(top) == {"branch_0_fun.3": pytest.approx(20 / 1e9),
                         "fusion.1": pytest.approx(20 / 1e9),
                         "copy.1": pytest.approx(2 / 1e9)}
    assert top[-1][0] == "copy.1"
    # an operation nested in another (a fusion in a loop's body) counts
    # its own time, and the outer one only what is left
    loop = ("%while.4 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) "
            "%tuple.1), condition=%c, body=%b", 100, 50)
    body = ("%fusion.9 = bf16[4,128]{1,0:T(8,128)} fusion(%p.1), kind=kLoop",
            110, 20)
    top = dict(tr.op_totals([loop, body], 0, 1000))
    assert top == {"while.4 tuple while": pytest.approx(30 / 1e9),
                   "fusion.9 bf16[4,128] fusion": pytest.approx(20 / 1e9)}
    assert tr.hlo_parts(body[0]) == ("fusion.9", "bf16[4,128]", "fusion")
    secs, n = tr.kernel_time(OPS, 0, 60, lambda n: n.startswith("branch"))
    assert n == 2 and secs == pytest.approx(20 / 1e9)
    # an event that leaves the window is not counted
    assert tr.kernel_time(OPS, 0, 52, lambda n: n.startswith("branch"))[1] == 1


def test_percentile_matches_numpy_and_counts_missing():
    rng = np.random.default_rng(0)
    xs = list(rng.exponential(1.0, 101))
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([1, 2, 3, math.inf], 50) == 2.5
    assert math.isinf(stats.percentile([1.0] * 18 + [math.inf] * 2, 95))
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_tpot_and_window():
    assert stats.tpot(1.0, 2.0, 11) == pytest.approx(0.1)
    assert stats.tpot(1.0, 1.0, 1) is None
    recs = [types.SimpleNamespace(due=t) for t in (0.5, 1.0, 1.5, 2.0)]
    assert [r.due for r in stats.due_in(recs, 1.0, 2.0)] == [1.0, 1.5]
    assert stats.rate(30, 10.0, 13.0) == pytest.approx(10.0)


def _run(**kw):
    base = dict(window=(10.0, 20.0), records=[], steps=[],
                tokens_in_window=500, counters={}, slots=4, peaks=None,
                trace=None, conf={"hidden_size": 2048,
                                  "intermediate_size": 8192})
    base.update(kw)
    r = types.SimpleNamespace(**base)
    r.due_in_window = lambda: [x for x in r.records
                               if r.window[0] <= x.due < r.window[1]]
    r.steps_in_window = lambda: [s for s in r.steps
                                 if s.t0 >= r.window[0] and s.t1 <= r.window[1]]
    return r


def test_request_readers():
    rec = lambda due, first, last, n, done=True, adm=None: types.SimpleNamespace(
        due=due, first=first, last=last, tokens=[0] * n, done=done,
        admitted=adm if adm is not None else due + 0.01)
    recs = [rec(10.0 + i * 0.1, 10.2 + i * 0.1, 11.2 + i * 0.1, 11)
            for i in range(20)]
    recs.append(rec(25.0, 25.1, 25.2, 2))              # due after the window
    run = _run(records=recs)
    assert readers.ttft_p95_ms(run) == pytest.approx(200.0)
    assert readers.tpot_p95_ms(run) == pytest.approx(100.0)
    assert readers.queue_wait_p95_ms(run) == pytest.approx(10.0)
    assert readers.output_tok_s(run) == pytest.approx(50.0)
    recs[3].done = False                 # unfinished, first token known
    recs[4].done = False
    assert readers.ttft_p95_ms(run) == pytest.approx(200.0)
    assert math.isinf(readers.tpot_p95_ms(run))
    recs[3].first = recs[4].first = None                # missing: tail
    assert math.isinf(readers.ttft_p95_ms(run))


def test_glu_roofline_share():
    peaks = peaks_for("TPU v5 lite")
    f, b = flops.glu_call(512, 2048, 8192)
    t_least, bound = flops.least_time(f, b, peaks)
    assert bound == "compute"
    assert flops.least_time(*flops.glu_call(32, 2048, 8192), peaks)[1] == "memory"
    # four calls of M=512 inside the traced window, each taking twice the
    # least time on the device
    dur = 2 * t_least * 1e9
    glu = ('%branch_0_fun.7 = bf16[512,8192]{1,0:T(8,128)(2,1)} custom-call('
           '%x.1, %a.1, %b.1, %constant.1), custom_call_target="tpu_custom_call"')
    ops = [(glu, 1000 + i * dur * 2, dur) for i in range(4)]
    ops.append(("%fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop", 0, 10))
    step = types.SimpleNamespace(t0=1.0, t1=2.0, glu_rows=[(512, 4)])
    trace = types.SimpleNamespace(ops=ops, spans=[], lo=0.0, hi=1e12,
                                  t0=0.5, t1=3.0)
    run = _run(steps=[step], trace=trace, peaks=peaks)
    assert readers.glu_roofline(run, log=lambda *a: None) == pytest.approx(50.0)
    assert readers.glu_roofline(_run(trace=None, peaks=peaks)) is None
    # a trace that holds another number of the kernel's events than the
    # calls issued, or none, is an error
    trace.ops = ops[1:]
    with pytest.raises(RuntimeError, match="issued 4 calls"):
        readers.glu_roofline(run, log=lambda *a: None)
    # XLA's own custom calls are not the kernel's
    trace.ops = ops[-1:] + [("%custom-call.14 = bf16[16,8]{1,0} custom-call("
                             "%p), custom_call_target=\"x\"", 0, 10)]
    with pytest.raises(RuntimeError, match=r"holds 0 branch_\* custom-call events"):
        readers.glu_roofline(run, log=lambda *a: None)


def test_peaks_table_is_keyed_by_kind():
    assert PEAKS["TPU v5 lite"].bf16_flops_per_s == 197e12
    assert PEAKS["TPU v5 lite"].hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_span_flops_is_the_sum_of_token_flops():
    c = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512}
    want = sum(flops.token_flops(c, p + 1) for p in range(7, 7 + 9))
    assert flops.span_flops(c, 7, 9) == pytest.approx(want)
