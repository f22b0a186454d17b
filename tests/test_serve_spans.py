"""The engine's spans and the timers they feed.

Every ``ServeEngine.step`` that has work records one ``engine.step``
span holding its admission, page upkeep, dispatches, host waits and
harvest; ``EngineStats``' timers are sums of those spans, and the
programs the spans dispatch carry names the device trace can find.
"""
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as M
from repro.serve import EngineConfig, ServeEngine
from repro.serve import engine as engine_mod
from repro.serve.spans import SPAN_CAP, SpanRecorder

STEP_CHILDREN = {"engine.admit", "engine.pages", "engine.dispatch",
                 "engine.wait", "engine.harvest"}


@pytest.fixture(scope="module")
def model():
    cfg = registry.get("qwen3-0.6b", smoke=True)
    params, _ = M.materialize_params(cfg, seed=0)
    return cfg, params


def prompts_of(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


def serve(cfg, params, lens=(9, 23, 5, 17), gen=6, **ecfg_kw):
    ecfg_kw.setdefault("slots", 2)
    ecfg_kw.setdefault("chunk", 4)
    ecfg_kw.setdefault("page_size", 5)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_prompt_len=32, max_len=32 + gen, **ecfg_kw))
    for p in prompts_of(cfg, lens):
        eng.submit(p, max_new=gen)
    done = eng.run()
    return eng, done


SCHEDULES = {"chunked": {"chunk_prefill": 7}, "one_shot": {}}


def spans_of(eng, name, **attrs):
    return [s for s in eng.spans if s.name == name
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def total(spans):
    return sum(s.t1 - s.t0 for s in spans)


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_spans_nest_as_listed(model, schedule):
    eng, done = serve(*model, **SCHEDULES[schedule])
    assert len(done) == 4
    steps = spans_of(eng, "engine.step")
    assert steps and all(s.parent is None for s in steps)
    assert [s.attrs["step"] for s in steps] == list(range(len(steps)))
    for s in eng.spans:
        if s.name == "engine.step":
            continue
        assert s.name in STEP_CHILDREN, s
        if schedule == "one_shot" and s.attrs.get("kind") in (
                "prefill", "insert"):
            assert s.parent == "engine.admit", s    # one-shot admission
        else:
            assert s.parent == "engine.step", s
        assert s.t0 <= s.t1
        assert len([p for p in steps if p.t0 <= s.t0 and s.t1 <= p.t1]) == 1
    for st in steps:
        inside = [s for s in eng.spans
                  if s.parent is not None and st.t0 <= s.t0 <= st.t1]
        decodes = [s for s in inside if s.name == "engine.dispatch"
                   and s.attrs["kind"] == "decode"]
        chunks = [s for s in inside if s.name == "engine.dispatch"
                  and s.attrs.get("program") == "prefill_chunk"]
        assert st.attrs["n_slots"] == sum(d.attrs["n_slots"] for d in decodes)
        assert st.attrs["n_steps"] == sum(d.attrs["n_steps"] for d in decodes)
        assert st.attrs["n_chunks"] == len(chunks)
        assert [s.name for s in inside].count("engine.admit") == 1


def test_prefill_dispatch_spans_carry_uid(model):
    cfg, params = model
    lens = (9, 23, 5, 17)
    eng, done = serve(cfg, params, lens, chunk_prefill=7)
    chunks = spans_of(eng, "engine.dispatch", kind="prefill")
    assert chunks and all(s.attrs["program"] == "prefill_chunk"
                          for s in chunks)
    by_uid = {}
    for s in chunks:
        by_uid.setdefault(s.attrs["uid"], []).append(s.attrs)
    assert sorted(by_uid) == [c.uid for c in done]
    for uid, n in enumerate(lens):
        a = by_uid[uid]
        # the request's chunks cover its prompt once, in order, from one slot
        assert [x["pos"] for x in a] == list(
            np.cumsum([0] + [x["tokens"] for x in a])[:-1])
        assert sum(x["tokens"] for x in a) == n
        assert len({x["slot"] for x in a}) == 1
        assert all(x["tokens"] <= x["bucket"] <= 7 for x in a)
    firsts = spans_of(eng, "engine.wait", kind="first_token")
    assert sorted(s.attrs["uid"] for s in firsts) == [c.uid for c in done]
    assert eng.stats.prefill_chunks == len(chunks)
    assert eng.stats.prefill_tokens == sum(lens)


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_timers_equal_span_sums(model, schedule):
    eng, _ = serve(*model, **SCHEDULES[schedule])
    st = eng.stats
    kinds = {"decode": st.decode_s, "prefill": st.prefill_s,
             "insert": st.insert_s}
    for kind, timer in kinds.items():
        spans = [s for s in eng.spans if s.attrs.get("kind") == kind
                 and s.name in ("engine.dispatch", "engine.wait")]
        assert timer == pytest.approx(total(spans), rel=1e-12, abs=0), kind
    assert st.decode_s > 0 and st.prefill_s > 0
    assert (st.insert_s > 0) == (schedule == "one_shot")
    assert st.wait_s == pytest.approx(total(spans_of(eng, "engine.wait")),
                                      rel=1e-12)
    worked = [s for s in spans_of(eng, "engine.step")
              if s.attrs["n_slots"] + s.attrs["n_chunks"] > 0]
    assert st.step_s == pytest.approx(total(worked), rel=1e-12)
    assert st.wait_s < st.step_s
    assert st.decode_chunks == len(spans_of(eng, "engine.dispatch",
                                            kind="decode"))
    assert st.decode_steps == sum(
        s.attrs["n_steps"] for s in spans_of(eng, "engine.dispatch",
                                             kind="decode"))


def test_buffer_stays_at_its_bound(model, monkeypatch):
    cfg, params = model
    eng, _ = serve(cfg, params, lens=(5,), gen=2)
    assert eng.spans.maxlen == SPAN_CAP
    cap = 40
    monkeypatch.setattr(engine_mod, "SpanRecorder",
                        functools.partial(SpanRecorder, cap=cap))
    eng, done = serve(cfg, params, lens=(9, 23, 5, 17, 30, 11, 2, 28),
                      gen=12, chunk_prefill=5, chunk=2)
    assert len(done) == 8
    assert len(eng.spans) == cap
    # the newest spans are kept: the last is the last step
    assert eng.spans[-1].name == "engine.step"
    assert eng.spans[-1].attrs["step"] == eng._n_step - 1
    assert eng._n_step * 5 > cap


def test_idle_step_records_nothing(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_prompt_len=16, max_len=24, page_size=5,
        chunk_prefill=4))
    assert eng.step() is False
    assert len(eng.spans) == 0 and eng.stats.step_s == 0.0


def test_attrs_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    rec = SpanRecorder()
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("engine.step", step=3) as sp:
            with rec.span("engine.wait", kind="decode"):
                f(jnp.ones(4)).block_until_ready()
            sp.attrs["n_slots"] = 2
    [path] = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                       recursive=True)
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    stats[e.name] = dict(e.stats)
    assert stats["engine.step"] == {"step": 3, "n_slots": 2}
    assert stats["engine.wait"] == {"kind": "decode"}
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("engine.wait", "engine.step"), ("engine.step", None)]


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split(" ", 1)[0]


def test_lowered_programs_are_named(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_prompt_len=16, max_len=24, page_size=4,
        chunk_prefill=8))
    dec = eng._decode_at(eng.ecfg.chunk).lower(eng.params, eng.cache,
                                               eng.state)
    assert _module_name(dec) == "jit_decode_chunk"
    i32 = jnp.int32
    pf = eng._chunk_at(8).lower(
        eng.params, eng.cache, eng.state, {"tokens": jnp.zeros((1, 8), i32)},
        i32(0), i32(0), i32(3), jnp.asarray(True), jnp.asarray(True),
        i32(0), eng._base_key, jnp.zeros((1,), jnp.float32), i32(4), i32(-1))
    assert _module_name(pf) == "jit_prefill_chunk"
    one_shot = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_prompt_len=16, max_len=24))
    batch = {"tokens": jnp.zeros((1, 16), i32),
             "lengths": jnp.full((1,), 5, i32)}
    lo = one_shot._prefill.lower(one_shot.params, batch, jnp.zeros(1, i32),
                                 one_shot._base_key,
                                 jnp.zeros((1,), jnp.float32))
    assert _module_name(lo) == "jit_prefill_sample"
    assert one_shot._insert.__name__ == "insert"


def test_prefix_prefill_program_is_named(model):
    cfg, _ = model
    raw = engine_mod.make_prefix_prefill_sample(cfg, 1, 4, 16)
    assert raw.__name__ == "prefix_prefill_sample"


def test_serve_batch_reports_wait_and_host(model):
    from repro.launch.serve import serve_batch
    cfg, params = model
    prompts = jnp.asarray(np.stack(prompts_of(cfg, (12, 12, 12))))
    for kw in ({}, {"chunk_prefill": 4}):
        _, stats = serve_batch(cfg, params, prompts, 5, **kw)
        assert 0.0 < stats.wait_s < stats.step_s, kw
        assert stats.decode_s <= stats.step_s
