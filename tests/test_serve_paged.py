"""Paged KV cache tests: page pool, prefix reuse, and slot/paged A/B.

The paged contract's guarantee is that paging is INVISIBLE to the
decoded tokens: the page pool + page-table indirection is a memory
layout change, so a greedy request served through the paged engine
emits token-for-token what the legacy per-slot engine emits — including
sliding-window rings whose write position wraps past page boundaries,
and page sizes that do not divide the ring capacity. On top of that
sit the pool's own invariants: reservations make lazy growth
infallible, prefix pages are refcounted and revivable, and admission
backpressures instead of over-committing pages.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.common import act_layers_of
from repro.launch import steps as steps_mod
from repro.models import model as M
from repro.serve import EngineConfig, ServeEngine
from repro.serve.paging import PagePool


def setup(arch, **cfg_over):
    cfg = registry.get(arch, smoke=True)
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    params, _ = M.materialize_params(cfg, seed=0)
    return cfg, params


def make_prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


def serve(cfg, params, prompts, gen, *, max_prompt=32, **ecfg_kw):
    ecfg_kw.setdefault("slots", 2)
    ecfg_kw.setdefault("chunk", 4)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_prompt_len=max_prompt, max_len=max_prompt + gen, **ecfg_kw))
    for p in prompts:
        eng.submit(p, max_new=gen)
    return eng.run(), eng


def token_streams(done):
    return {c.uid: c.tokens for c in done}


class TestPagePool:
    def test_alloc_never_hands_out_trash_and_frees_recycle(self):
        p = PagePool(n_pages=6, page_size=4)
        a = p.alloc(5)
        assert a is not None and 0 not in a and len(set(a)) == 5
        assert p.alloc(1) is None and p.in_use == 5
        p.release(a[:2])
        b = p.alloc(2)
        assert b is not None and 0 not in b
        assert p.in_use == 5 and p.available() == 0

    def test_alloc_respects_reservations(self):
        """A direct alloc must not eat pages reserved for other slots'
        growth — that reservation is the deadlock-freedom invariant."""
        p = PagePool(n_pages=8, page_size=4)
        assert p.reserve(5)
        assert p.alloc(3) is None          # only 7 usable, 5 reserved
        a = p.alloc(2)
        assert a is not None
        g = p.alloc_reserved(5)            # growth draws on the reservation
        assert g is not None and len(g) == 5
        assert p.available() == 0 and p.in_use == 7

    def test_reserve_refuses_overcommit(self):
        p = PagePool(n_pages=4, page_size=2)
        assert p.reserve(3)
        assert not p.reserve(1)
        p.unreserve(3)
        assert p.available() == 3

    def test_register_match_share_release_refcount(self):
        p = PagePool(n_pages=8, page_size=4)
        toks = list(range(12))              # 3 full pages
        a = p.alloc(3)
        p.register(toks, a)
        assert p.match(toks, limit=3) == a
        assert p.match(toks, limit=2) == a[:2]
        assert p.match(toks[:11], limit=2) == a[:2]   # chain keyed per page
        assert p.match([99] + toks[1:], limit=3) == []
        p.release(a)                        # ref 0 -> parked, still matchable
        assert p.in_use == 0
        assert p.match(toks, limit=3) == a
        p.share(a)                          # revive from the parked pool
        assert p.in_use == 3
        p.share(a)
        p.release(a)
        assert p.in_use == 3                # second ref still held
        p.release(a)
        assert p.in_use == 0

    def test_parked_chains_evict_lru_only_when_free_runs_dry(self):
        p = PagePool(n_pages=6, page_size=2)
        a, b = p.alloc(2), p.alloc(2)
        p.register([1, 2, 3, 4], a)
        p.register([5, 6, 7, 8], b)
        p.release(a)
        p.release(b)
        # free list is dry (5 usable, 4 parked, 1 free) -> second alloc
        # must evict the least-recently parked page, which is a's head:
        # chain a is broken at page 0, chain b untouched
        got = p.alloc(2)
        assert got is not None
        assert p.match([1, 2, 3, 4], limit=2) == []
        assert p.match([5, 6, 7, 8], limit=2) == b

    def test_eviction_order_is_lru(self):
        p = PagePool(n_pages=5, page_size=2)
        a, b = p.alloc(2), p.alloc(2)
        p.register([1, 2], a[:1])
        p.register([3, 4], b[:1])
        p.release(a)                        # a[0] parked, a[1] -> free
        p.release(b)                        # b[0] parked, b[1] -> free
        p.share(a[:1])                      # touch a -> b[0] is now LRU
        p.release(a[:1])
        p.alloc(3)                          # 2 free + 1 eviction (b[0])
        assert p.match([3, 4], limit=1) == []
        assert p.match([1, 2], limit=1) == a[:1]


PAGED_ARCHS = ["qwen3-0.6b", "qwen2-vl-2b", "mixtral-8x22b", "hymba-1.5b"]


class TestPagedSlotIdentity:
    @pytest.mark.parametrize("arch", PAGED_ARCHS)
    def test_paged_matches_slot_greedy(self, arch):
        """Paged vs legacy slot cache A/B on the same staggered workload.
        mixtral (sliding_window=32 in smoke) decodes far enough that the
        ring write position wraps past page_size several times; the page
        size (5) deliberately divides neither the window nor the
        power-of-two buckets, so the ring is padded to whole pages and
        the pad region must stay masked out. hymba carries the pool
        through the layer scan beside per-layer conv/ssm state."""
        cfg, params = setup(arch)
        prompts = make_prompts(cfg, [9, 17, 30, 12], seed=3)
        gen = 40 if cfg.sliding_window else 10
        base, _ = serve(cfg, params, prompts, gen, cache="slot")
        paged, eng = serve(cfg, params, prompts, gen, cache="paged",
                           page_size=5)
        assert eng.paged
        if cfg.sliding_window:
            # the wrap actually happened: decode advanced past the ring
            assert max(len(p) for p in prompts) + gen > eng._w_pad
        assert token_streams(paged) == token_streams(base)

    def test_page_size_one_and_large(self):
        """Degenerate page sizes: ps=1 (a page per token — maximal table
        indirection) and ps >= capacity (a single page per slot — the
        slot layout re-derived through the table) both stay identical."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [11, 6], seed=4)
        base, _ = serve(cfg, params, prompts, 8, cache="slot")
        for ps in (1, 64):
            paged, _ = serve(cfg, params, prompts, 8, cache="paged",
                             page_size=ps)
            assert token_streams(paged) == token_streams(base), ps

    def test_decode_writes_each_layer_at_its_global_index(self):
        """One paged decode step under a two-segment LayerEngines
        assignment: every layer l holds layer l's new K/V at
        [l, page, off] for the live rows (the slot cache's step, from
        the same rings, is the reference), and the row the write mask
        freezes keeps its pages, cur and k_pos bit-identical."""
        base = registry.get("qwen3-0.6b", smoke=True, n_layers=4)
        cfg = act_layers_of(base, ("cr-d32", "cr-d32", "pwl-d16", "pwl-d16"))
        params, _ = M.materialize_params(cfg, seed=0)
        engine = steps_mod.make_engine(cfg)
        assert [(s, t) for s, t, _ in engine.segments] == [(0, 2), (2, 4)]

        B, ps, n, n_pages = 3, 4, 3, 12
        cache = M.init_paged_cache(cfg, B, n_pages, ps, n * ps)
        W = cache["k_pos"].shape[1]
        rng = np.random.RandomState(11)
        for name in ("k", "v"):
            shape = cache["layers"][name].shape
            cache["layers"][name] = jnp.asarray(
                rng.normal(size=shape), cache["layers"][name].dtype)
        tbl = np.arange(1, 1 + B * n, dtype=np.int32).reshape(B, n)
        cur = np.array([3, 7, 5], np.int32)
        j = np.arange(W)
        cache["page_tbl"] = jnp.asarray(tbl)
        cache["cur"] = jnp.asarray(cur)
        cache["k_pos"] = jnp.asarray(
            np.where(j[None, :] < cur[:, None], j[None, :], -1), jnp.int32)
        live = np.array([True, False, True])
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)),
                             jnp.int32)
        x = M.embed_tokens(params, tokens, cfg)
        _, paged = M.run_stack_decode(
            params, x, {"tokens": tokens, "write_mask": jnp.asarray(live)},
            cfg, engine, cache)

        rings = {name: cache["layers"][name][:, tbl].reshape(
            (cfg.n_layers, B, W) + cache["layers"][name].shape[3:])
            for name in ("k", "v")}
        slot_cache = {"layers": rings, "cur": cache["cur"],
                      "k_pos": cache["k_pos"]}
        _, slot = M.run_stack_decode(params, x, {"tokens": tokens}, cfg,
                                     engine, slot_cache)

        page, off = tbl[np.arange(B), (cur % W) // ps], (cur % W) % ps
        for name in ("k", "v"):
            new_pool = np.asarray(paged["layers"][name])
            want = np.asarray(slot["layers"][name])
            old_pool = np.asarray(cache["layers"][name])
            for b in np.flatnonzero(live):
                np.testing.assert_array_equal(
                    new_pool[:, page[b], off[b]], want[:, b, cur[b] % W])
                assert not np.array_equal(new_pool[:, page[b], off[b]],
                                          old_pool[:, page[b], off[b]])
            frozen = tbl[~live].ravel()
            np.testing.assert_array_equal(new_pool[:, frozen],
                                          old_pool[:, frozen])
        np.testing.assert_array_equal(np.asarray(paged["cur"]),
                                      cur + live.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(paged["k_pos"])[~live],
                                      np.asarray(cache["k_pos"])[~live])
        np.testing.assert_array_equal(np.asarray(paged["page_tbl"]), tbl)

    def test_ssm_arch_falls_back_to_slot(self):
        """Pure-SSM archs have no KV ring to page; cache='paged' must
        serve them on the legacy contract rather than fail."""
        cfg, params = setup("falcon-mamba-7b")
        prompts = make_prompts(cfg, [9, 13], seed=5)
        base, _ = serve(cfg, params, prompts, 6, cache="slot")
        paged, eng = serve(cfg, params, prompts, 6, cache="paged")
        assert not eng.paged and not eng.prefix_enabled
        assert token_streams(paged) == token_streams(base)


class TestPrefixReuse:
    def test_prefix_hit_matches_cold_and_counts_tokens(self):
        """Two requests sharing a long page-aligned prompt prefix,
        admitted serially: the second must prefill only its suffix
        (prefix_hit_tokens counts the skipped pages) and still emit the
        cold-path tokens exactly."""
        cfg, params = setup("qwen3-0.6b")
        ps = 8
        rng = np.random.RandomState(7)
        shared = rng.randint(0, cfg.vocab_size, (2 * ps,)).astype(np.int32)
        tails = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                 for n in (5, 9)]
        prompts = [np.concatenate([shared, t]) for t in tails]
        cold, _ = serve(cfg, params, prompts, 8, prefix_cache=False,
                        admission="serial")
        warm, eng = serve(cfg, params, prompts, 8, prefix_cache=True,
                          page_size=ps, admission="serial")
        assert eng.prefix_enabled
        # request 0 is cold; request 1 hits both shared pages
        assert eng.stats.prefix_hit_tokens == 2 * ps
        assert 0.0 < eng.stats.prefix_hit_rate < 1.0
        assert token_streams(warm) == token_streams(cold)

    def test_identical_prompts_batched_share_one_chain(self):
        """Same-prompt requests admitted in ONE batch share the chain
        registered by... nobody yet — they're all cold together. The
        next wave over the same prompt then hits. Tokens stay identical
        to the prefix-off engine throughout."""
        cfg, params = setup("qwen3-0.6b")
        ps = 8
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, cfg.vocab_size, (3 * ps + 3,)).astype(np.int32)
        prompts = [prompt.copy() for _ in range(4)]
        cold, _ = serve(cfg, params, prompts, 6, prefix_cache=False)
        warm, eng = serve(cfg, params, prompts, 6, prefix_cache=True,
                          page_size=ps)
        # waves after the first hit the full (L-1)//ps-page chain
        assert eng.stats.prefix_hit_tokens > 0
        assert token_streams(warm) == token_streams(cold)

    def test_sliding_window_disables_prefix_not_paging(self):
        cfg, params = setup("mixtral-8x22b")
        eng = ServeEngine(cfg, params, EngineConfig(
            slots=2, max_prompt_len=32, max_len=48, cache="paged",
            prefix_cache=True))
        assert eng.paged and not eng.prefix_enabled


class TestPagePressure:
    def test_exhaustion_backpressures_and_completes_all(self):
        """A pool sized for ~one request at a time: admission must wait
        for decode to free pages (never over-commit), and every request
        still completes with the ample-pool tokens."""
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [20, 18, 25, 9], seed=6)
        gen = 8
        ample, _ = serve(cfg, params, prompts, gen, slots=4)
        n_slot = M.pages_per_slot(cfg, 32 + gen, 16)
        tight, eng = serve(cfg, params, prompts, gen, slots=4,
                           page_size=16, n_pages=n_slot + 2,
                           prefix_cache=False)
        assert token_streams(tight) == token_streams(ample)
        assert eng.stats.pages_peak <= n_slot + 1
        assert eng.stats.pages_in_use == 0          # all freed at drain

    def test_all_pages_freed_after_run(self):
        cfg, params = setup("qwen3-0.6b")
        prompts = make_prompts(cfg, [9, 17, 30, 12, 5], seed=9)
        done, eng = serve(cfg, params, prompts, 8, slots=3)
        assert len(done) == len(prompts)
        assert eng._pool.in_use == 0
        assert eng._pool.reserved == 0
        # every non-trash page is either free or parked on a prefix
        # chain — available() sees all of them
        assert eng._pool.available() == eng._n_pages - 1
        assert eng.stats.pages_peak > 0

    def test_n_pages_must_cover_one_slot(self):
        cfg, params = setup("qwen3-0.6b")
        with pytest.raises(ValueError, match="n_pages"):
            ServeEngine(cfg, params, EngineConfig(
                slots=2, max_prompt_len=32, max_len=40, cache="paged",
                page_size=16, n_pages=2))
