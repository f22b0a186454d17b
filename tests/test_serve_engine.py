"""Continuous-batching serve engine tests.

The central guarantee: a request served through the engine — bucketed
ragged prefill, a shared fixed-slot decode batch at whatever position
its neighbors happen to be, admission mid-flight into a recycled slot —
emits token-for-token (greedy) what the same request produces served
alone through the lockstep prefill/decode reference path.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.activations import ActivationConfig, ActivationEngine
from repro.models import model as M
from repro.serve import EngineConfig, ServeEngine, bucket_len
from repro.serve.scheduler import FifoScheduler, Request, SlotRun


def lockstep_reference(cfg, params, prompt, gen, capacity):
    """Per-request greedy reference: scalar-`cur` prefill + one decode_fn
    call per token (the pre-engine serving contract)."""
    eng = ActivationEngine(cfg.activation)
    logits, cache = M.prefill_fn(
        params, {"tokens": jnp.asarray(prompt[None, :])}, cfg, eng,
        capacity=capacity)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [int(tok[0])]
    for _ in range(gen - 1):
        logits, cache = M.decode_fn(params, {"tokens": tok[:, None]},
                                    cache, cfg, eng)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


def make_prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


def setup(arch, **cfg_over):
    cfg = registry.get(arch, smoke=True)
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    params, _ = M.materialize_params(cfg, seed=0)
    return cfg, params


def serve(cfg, params, prompts, gen, *, slots=2, chunk=4, max_prompt=64,
          admission="batched", **submit_kw):
    eng = ServeEngine(cfg, params, EngineConfig(
        slots=slots, max_prompt_len=max_prompt, max_len=max_prompt + gen,
        chunk=chunk, admission=admission))
    for p in prompts:
        eng.submit(p, max_new=gen, **submit_kw)
    return eng.run(), eng


class TestStaggeredAdmission:
    def test_matches_lockstep_reference_token_for_token(self):
        """5 variable-length requests through 2 slots: requests are
        admitted into slots whose neighbors are mid-generation, yet each
        greedy stream must equal its solo lockstep reference exactly."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [9, 17, 30, 12, 5])
        gen = 10
        done, eng = serve(cfg, params, prompts, gen)
        assert [c.uid for c in done] == list(range(5))
        for c, p in zip(done, prompts):
            ref = lockstep_reference(cfg, params, p, gen, eng.capacity)
            assert c.tokens == ref, (c.uid, c.tokens, ref)
            assert c.finish_reason == "length"

    def test_mrope_per_slot_positions_b2(self):
        """qwen2-vl-style decode: per-slot positions must drive all three
        M-RoPE sections independently per batch row (the old decode path
        hard-coded a (1, 1, 3) broadcast — correct only for B == 1 or
        lockstep batches)."""
        cfg, params = setup("qwen2-vl-2b")
        prompts = make_prompts(cfg, [7, 19, 13], seed=2)
        gen = 6
        done, eng = serve(cfg, params, prompts, gen)
        for c, p in zip(done, prompts):
            ref = lockstep_reference(cfg, params, p, gen, eng.capacity)
            assert c.tokens == ref, (c.uid, c.tokens, ref)

    def test_single_token_request_frees_slot_for_queue(self):
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [8, 11, 9])
        eng = ServeEngine(cfg, params, EngineConfig(
            slots=1, max_prompt_len=32, max_len=40, chunk=2))
        eng.submit(prompts[0], max_new=1)
        eng.submit(prompts[1], max_new=4)
        eng.submit(prompts[2], max_new=1)
        done = eng.run()
        assert [len(c.tokens) for c in done] == [1, 4, 1]
        assert all(c.finish_reason == "length" for c in done)


class TestPerSlotEos:
    def test_eos_stops_one_slot_without_disturbing_neighbors(self):
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [10, 21], seed=1)
        gen = 12
        # learn request 0's greedy stream, then pick as EOS a token whose
        # FIRST occurrence in it is at a known index (greedy streams
        # repeat tokens) and which request 1 never emits
        base, eng = serve(cfg, params, prompts, gen)
        eos = stop_at = None
        for k in range(2, gen):
            t = base[0].tokens[k]
            if t not in base[0].tokens[:k] and t not in base[1].tokens:
                eos, stop_at = t, k
                break
        assert eos is not None, (base[0].tokens, base[1].tokens)
        done, _ = serve(cfg, params, prompts, gen, eos_id=eos)
        assert done[0].finish_reason == "eos"
        assert done[0].tokens == base[0].tokens[:stop_at + 1]  # incl. eos
        assert done[1].finish_reason == "length"
        assert done[1].tokens == base[1].tokens         # neighbor untouched


class TestSlidingWindowRing:
    def test_ring_cache_per_slot_beyond_window(self):
        """mixtral-smoke (window 32): prompts longer than the window plus
        generation force ring wraparound at per-slot offsets; staggered
        engine output must equal each request's solo reference."""
        cfg, params = setup("mixtral-8x22b")
        assert cfg.sliding_window == 32
        prompts = make_prompts(cfg, [40, 44, 35], seed=3)
        gen = 8
        done, eng = serve(cfg, params, prompts, gen)
        for c, p in zip(done, prompts):
            ref = lockstep_reference(
                cfg, params, p, gen, M.cache_capacity(cfg, len(p) + gen))
            assert c.tokens == ref, (c.uid, c.tokens, ref)


class TestSamplingAndBackends:
    def test_temperature_sampling_path_runs(self):
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [8, 14, 11], seed=5)
        done, _ = serve(cfg, params, prompts, 8, temperature=0.8)
        assert len(done) == 3
        for c in done:
            assert len(c.tokens) == 8
            assert all(0 <= t < cfg.padded_vocab for t in c.tokens)

    def test_temperature_streams_schedule_invariant(self):
        """Sampling keys are fold_in(fold_in(base, uid), index): a pure
        function of the request and token position. Serial vs batched
        admission, trimmed vs untrimmed drain and slot count must all
        emit identical temperature>0 streams, and re-running the same
        workload must reproduce them exactly."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [9, 17, 30, 12, 5], seed=6)
        gen = 10
        base, _ = serve(cfg, params, prompts, gen, temperature=0.7)
        streams = {c.uid: c.tokens for c in base}
        for kw in ({"admission": "serial"}, {"slots": 3}, {"chunk": 7}):
            done, _ = serve(cfg, params, prompts, gen, temperature=0.7, **kw)
            assert {c.uid: c.tokens for c in done} == streams, kw
        again, _ = serve(cfg, params, prompts, gen, temperature=0.7)
        assert {c.uid: c.tokens for c in again} == streams

    def test_cr_fixed_engine_serves_unchanged(self):
        """The Q2.13 fixed-point activation datapath must serve through
        the engine exactly as it does through the lockstep reference —
        the serving layer is activation-impl-agnostic."""
        cfg, params = setup(
            "qwen3-0.6b",
            activation=ActivationConfig(impl="cr_fixed", depth=32))
        prompts = make_prompts(cfg, [9, 16], seed=7)
        gen = 8
        done, eng = serve(cfg, params, prompts, gen)
        for c, p in zip(done, prompts):
            ref = lockstep_reference(cfg, params, p, gen, eng.capacity)
            assert c.tokens == ref, (c.uid, c.tokens, ref)


class TestBatchedAdmission:
    def test_batched_matches_serial_token_for_token(self):
        """Bucket-grouped multi-row admission (one ragged prefill dispatch
        + one multi-row insert per round) must emit exactly what
        one-request-at-a-time admission emits, request by request."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [9, 12, 17, 30, 5, 11, 13, 8], seed=4)
        gen = 8
        done_b, eng_b = serve(cfg, params, prompts, gen, slots=4)
        done_s, eng_s = serve(cfg, params, prompts, gen, slots=4,
                              admission="serial")
        assert [c.tokens for c in done_b] == [c.tokens for c in done_s]
        # batching must actually group: strictly fewer prefill dispatches
        # than requests, while serial admission pays one per request
        assert eng_s.stats.prefill_batches == len(prompts)
        assert eng_b.stats.prefill_batches < len(prompts)
        assert eng_b.stats.prefill_requests == len(prompts)

    def test_same_bucket_requests_admit_in_one_dispatch(self):
        """4 free slots + 4 same-bucket prompts -> exactly one prefill
        dispatch admits all of them."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [9, 10, 12, 14])    # all bucket 16
        done, eng = serve(cfg, params, prompts, 6, slots=4)
        assert eng.stats.prefill_batches == 1
        assert eng.stats.prefill_requests == 4
        for c, p in zip(done, prompts):
            ref = lockstep_reference(cfg, params, p, 6, eng.capacity)
            assert c.tokens == ref

    def test_exact_buckets_batch_equal_lengths_only(self):
        """SSM archs prefill at exact lengths; the batch pop groups only
        equal-length prompts, and outputs still match the reference."""
        cfg, params = setup("falcon-mamba-7b")
        prompts = make_prompts(cfg, [11, 11, 7, 11], seed=6)
        gen = 6
        done, eng = serve(cfg, params, prompts, gen, slots=4)
        # head bucket (len 11) groups the three 11s; the 7 admits alone
        assert eng.stats.prefill_batches == 2
        for c, p in zip(done, prompts):
            ref = lockstep_reference(cfg, params, p, gen, eng.capacity)
            assert c.tokens == ref, (c.uid, c.tokens, ref)


class TestServeBatchWrapper:
    def test_eos_ragged_completions_round_trip_padded(self):
        """serve_batch must survive rows stopping early: every returned
        row is right-padded with 0 to gen_tokens, the engine agrees with
        the lockstep benchmark reference, and pre-eos prefixes match the
        eos-free run."""
        from repro.launch.serve import (_mask_after_eos, _serve_batch_python,
                                        serve_batch)
        cfg, params = setup("qwen3-0.6b")
        rng = np.random.RandomState(9)
        prompts = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (3, 10)).astype(np.int32))
        gen = 10
        base, _ = serve_batch(cfg, params, prompts, gen)
        base = np.asarray(base)
        # pick an eos that actually truncates some row mid-stream
        eos = next(int(t) for t in base[:, 2:-1].reshape(-1) if t != 0)
        expected = _mask_after_eos(base, eos)
        assert (expected != base).any(), "eos must truncate something"
        toks, _ = serve_batch(cfg, params, prompts, gen, eos_id=eos)
        toks = np.asarray(toks)
        assert toks.shape == (3, gen)
        np.testing.assert_array_equal(toks, expected)
        ref, _ = _serve_batch_python(cfg, params, prompts, gen, eos_id=eos)
        np.testing.assert_array_equal(np.asarray(ref), expected)

    def test_mask_after_eos_matches_scalar_loop(self):
        """The vectorized cumsum mask reproduces the per-row scan: keep
        everything up to and including the FIRST eos, zero the rest —
        repeated eos hits and eos at the edges included."""
        from repro.launch.serve import _mask_after_eos
        rows = np.array([
            [3, 7, 7, 5, 2],     # eos (7) mid-row, repeated
            [7, 1, 2, 3, 4],     # eos first
            [1, 2, 3, 4, 7],     # eos last (nothing to zero)
            [1, 2, 3, 4, 5],     # no eos
            [7, 7, 7, 7, 7],     # all eos
        ], np.int32)
        expected = rows.copy()
        for b in range(rows.shape[0]):
            hits = np.nonzero(rows[b] == 7)[0]
            if hits.size:
                expected[b, hits[0] + 1:] = 0
        np.testing.assert_array_equal(_mask_after_eos(rows, 7), expected)
        # K-plane block: eos tested on codebook 0, whole positions zeroed
        planes = np.stack([rows, rows + 100], axis=-1)     # [B, gen, 2]
        masked = _mask_after_eos(planes, 7)
        np.testing.assert_array_equal(masked[..., 0], expected)
        np.testing.assert_array_equal(
            masked[..., 1], np.where(expected != 0, rows + 100, 0))

    def test_engine_matches_lockstep_reference(self):
        """serve_batch (always the engine now) and the benchmark-only
        lockstep reference share one sampling implementation: greedy
        streams agree token-for-token on the same workload."""
        from repro.launch.serve import _serve_batch_python, serve_batch
        cfg, params = setup("qwen3-0.6b")
        rng = np.random.RandomState(3)
        prompts = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (2, 12)).astype(np.int32))
        te, _ = serve_batch(cfg, params, prompts, 8)
        tp, _ = _serve_batch_python(cfg, params, prompts, 8)
        np.testing.assert_array_equal(np.asarray(te), np.asarray(tp))

    def test_ragged_prompt_list_matches_lockstep_per_length(self):
        """A list of prompts of their own lengths serves in one call; each
        row equals the lockstep reference run on its prompt alone."""
        from repro.launch.serve import _serve_batch_python, serve_batch
        cfg, params = setup("qwen3-0.6b")
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 12, 9)]
        te, stats = serve_batch(cfg, params, prompts, 6)
        assert te.shape == (3, 6) and stats.prompt_len == 12
        for row, p in zip(np.asarray(te), prompts):
            tp, _ = _serve_batch_python(cfg, params, jnp.asarray(p)[None], 6)
            np.testing.assert_array_equal(row, np.asarray(tp)[0])

    def test_serve_batch_has_no_backend_switch(self):
        """The python backend is retired from the serving path: serve_batch
        accepts no backend selector (the lockstep loop survives only as
        the benchmark reference `_serve_batch_python`)."""
        import inspect

        from repro.launch.serve import serve_batch
        assert "backend" not in inspect.signature(serve_batch).parameters

    def test_prefill_stats_guard_zero_division(self):
        from repro.launch.serve import ServeStats
        st = ServeStats(prefill_s=0.0, decode_s=0.0, n_prompts=2,
                        prompt_len=8, generated=1, decode_steps=0,
                        decode_tokens=0)
        assert st.prefill_tokens_per_s == 0.0
        assert st.decode_tokens_per_s == 0.0


class TestDrainTrim:
    def test_trimmed_drain_token_identical_and_fewer_steps(self):
        """Capping the final decode chunks at the largest surviving
        budget must not change a single emitted token (greedy) while
        running strictly fewer in-jit steps than the untrimmed path."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, (9, 14, 20), seed=3)
        gen, runs = 6, {}
        for trim in (True, False):
            eng = ServeEngine(cfg, params, EngineConfig(
                slots=2, max_prompt_len=32, max_len=32 + gen, chunk=8,
                trim_drain=trim))
            for p in prompts:
                eng.submit(p, max_new=gen)
            done = eng.run()
            runs[trim] = ([c.tokens for c in done], eng.stats.decode_steps)
        assert runs[True][0] == runs[False][0]
        assert runs[True][1] < runs[False][1], runs
        # gen 6 after the admission token: no slot ever needs more than
        # 5 decode steps, so no chunk should exceed that
        assert runs[True][1] <= 5 * 2

    def test_drain_compiles_at_most_one_extra_chunk_size(self):
        cfg, params = setup("qwen3-0.6b")
        gen = 6
        eng = ServeEngine(cfg, params, EngineConfig(
            slots=2, max_prompt_len=32, max_len=32 + gen, chunk=8))
        for p in make_prompts(cfg, (9, 14), seed=4):
            eng.submit(p, max_new=gen)
        eng.run()
        # lockstep budgets: the full chunk plus ONE drain size
        assert set(eng._decode_fns) == {8, 5}

    def test_untrimmed_config_keeps_single_chunk_size(self):
        cfg, params = setup("qwen3-0.6b")
        gen = 6
        eng = ServeEngine(cfg, params, EngineConfig(
            slots=2, max_prompt_len=32, max_len=32 + gen, chunk=8,
            trim_drain=False))
        for p in make_prompts(cfg, (9, 14), seed=4):
            eng.submit(p, max_new=gen)
        eng.run()
        assert set(eng._decode_fns) == {8}


class TestAdmissionStats:
    def test_insert_dispatch_is_timed(self):
        """The slot insert is half of admission: it must be timed into
        EngineStats.insert_s, and admission_tokens_per_s (prefill +
        insert) must not overstate the prefill-only rate."""
        cfg, params = setup("qwen3-0.6b")
        done, eng = serve(cfg, params, make_prompts(cfg, (9, 14), seed=5),
                          gen=4)
        assert len(done) == 2
        assert eng.stats.insert_s > 0.0
        assert (eng.stats.admission_tokens_per_s
                < eng.stats.prefill_tokens_per_s)
        # zero-division guards hold on a fresh stats object
        from repro.serve.engine import EngineStats
        assert EngineStats().admission_tokens_per_s == 0.0


class TestScheduler:
    def test_bucketing(self):
        assert bucket_len(9, min_bucket=16, max_len=64) == 16
        assert bucket_len(17, min_bucket=16, max_len=64) == 32
        assert bucket_len(33, min_bucket=16, max_len=64) == 64
        assert bucket_len(64, min_bucket=16, max_len=64) == 64
        assert bucket_len(21, min_bucket=16, max_len=64, exact=True) == 21
        # non-pow2 cap: the top bucket clamps to max_len itself
        assert bucket_len(33, min_bucket=16, max_len=48) == 48
        assert bucket_len(48, min_bucket=16, max_len=48) == 48
        # the error names the actual parameter, and the exact-length
        # (SSM) path validates identically to the pow2 path
        for exact in (False, True):
            with pytest.raises(ValueError, match="max_len"):
                bucket_len(65, min_bucket=16, max_len=64, exact=exact)

    def test_next_batch_groups_by_head_bucket(self):
        def bucket_of(req):
            return bucket_len(len(req.tokens), min_bucket=16, max_len=64)

        s = FifoScheduler(4)
        lens = [9, 30, 12, 14, 40, 10]      # buckets 16/32/16/16/64/16
        for i, n in enumerate(lens):
            s.submit(Request(uid=i, tokens=[0] * n, max_new=2))
        batch = s.next_batch(3, bucket_of)
        # head (uid 0, bucket 16) leads; uids 2 and 3 share its bucket
        assert [r.uid for r in batch] == [0, 2, 3]
        # the rest keep FIFO order; the new head's bucket (32) leads next
        assert [r.uid for r in s.queue] == [1, 4, 5]
        assert [r.uid for r in s.next_batch(4, bucket_of)] == [1]
        assert [r.uid for r in s.next_batch(4, bucket_of)] == [4]
        assert [r.uid for r in s.next_batch(4, bucket_of)] == [5]
        assert s.next_batch(4, bucket_of) == []

    def test_next_batch_full_batch_leaves_tail_untouched(self):
        """Once the batch is full the scan must STOP: the tail is never
        popped/re-appended (the old implementation rotated the whole
        queue through popleft/append on every admission round), and the
        requests left behind keep exact FIFO order."""
        calls = []

        def bucket_of(req):
            calls.append(len(req.tokens))
            return bucket_len(len(req.tokens), min_bucket=16, max_len=64)

        s = FifoScheduler(4)
        lens = [9, 30, 12, 14, 40, 10, 11, 13]  # buckets 16/32/16/16/64/16...
        for i, n in enumerate(lens):
            s.submit(Request(uid=i, tokens=[0] * n, max_new=2))
        tail_ids = [id(r) for r in list(s.queue)[4:]]   # uids 4..7
        batch = s.next_batch(3, bucket_of)
        assert [r.uid for r in batch] == [0, 2, 3]
        # uid 1 (bucket 32) was skipped and returns to the FRONT; the
        # tail beyond the fill point is untouched — same objects, same
        # order, and never even inspected by bucket_of
        assert [r.uid for r in s.queue] == [1, 4, 5, 6, 7]
        assert [id(r) for r in list(s.queue)[1:]] == tail_ids
        # head + the 4 popped requests = 5 bucket_of calls, not len(queue)
        assert len(calls) == 5

    def test_next_batch_respects_width(self):
        def bucket_of(req):
            return bucket_len(len(req.tokens), min_bucket=16, max_len=64)

        s = FifoScheduler(2)
        for i in range(5):
            s.submit(Request(uid=i, tokens=[0] * 8, max_new=2))
        assert [r.uid for r in s.next_batch(2, bucket_of)] == [0, 1]
        assert [r.uid for r in s.next_batch(2, bucket_of)] == [2, 3]
        assert [r.uid for r in s.next_batch(0, bucket_of)] == []
        assert [r.uid for r in s.next_batch(2, bucket_of)] == [4]

    def test_fifo_slot_lifecycle(self):
        s = FifoScheduler(2)
        reqs = [Request(uid=i, tokens=[1], max_new=2) for i in range(3)]
        for r in reqs:
            s.submit(r)
        assert s.free_slots() == [0, 1]
        s.bind(0, SlotRun(request=s.next_request(), tokens=[], admitted_at=0))
        s.bind(1, SlotRun(request=s.next_request(), tokens=[], admitted_at=0))
        assert s.free_slots() == [] and s.pending
        run = s.evict(0)
        assert run.request.uid == 0
        assert s.free_slots() == [0]
        s.bind(0, SlotRun(request=s.next_request(), tokens=[], admitted_at=0))
        assert s.slots[0].request.uid == 2
        s.evict(0), s.evict(1)
        assert not s.pending
