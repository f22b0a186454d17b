"""Continuous-batching serve engine: fixed decode slots, per-slot cache
positions, in-jit multi-token decode, batched-bucket admission.

The engine owns one per-slot KV/SSM cache of shape [B=slots, W] (cache
contract: models/model.py — `cur` [B], `k_pos` [B, W]) and runs decode as
a single jitted `lax.scan` over `chunk` steps: embedding, stack, sampling
and per-slot EOS/budget masking all happen on device, so the host pays
one dispatch + one sync per chunk instead of per token. Between chunks
the host harvests finished slots and admits queued requests into the
freed rows (iteration-level continuous batching; admission granularity =
`chunk` decode steps).

Admission is *batched by bucket*: the scheduler pops up to
`len(free_slots)` queued requests that share a prefill bucket
(power-of-two padded length; exact lengths for stateful archs) and the
engine prefills them in ONE ragged dispatch. The first token of every
admitted row is sampled on device inside that same dispatch — the host
syncs only the [N] int32 token vector (for the EOS / budget<=1
early-complete check), never the full-vocab logits. Admitted rows are
then scattered into the big cache with a single jitted, donated
multi-row slot insert. Slot writes replace the *entire* row (all W key
positions), so stale state from the previous occupant can never leak
into the new request's attention.

Cache contracts: by default (``EngineConfig(cache="paged")``) the KV
ring lives in a fixed page pool [n_pages, page_size, ...] shared by all
slots, with per-slot page tables mapping logical ring pages to physical
pages (models/model.py paged contract). Admission is bounded by *free
pages*, not free slots: prompt pages are allocated at admission (plus a
worst-case reservation so lazy growth during decode can never
deadlock), grown chunk-by-chunk as generation advances, and released at
completion — so capacity tracks actual usage instead of worst-case
context. Page-aligned common prompt prefixes are deduplicated via a
refcounted host-side registry (paging.py): a hit admits those tokens
without prefilling them, attending suffix queries over the cached
pages. ``cache="slot"`` keeps the legacy one-full-ring-per-slot
contract for A/B benchmarking.

Token-budget schedule (``EngineConfig(chunk_prefill=N)``, paged
attention archs only): instead of the phase-separated admit-then-decode
loop above — where one whole-prompt prefill dispatch stalls every
decoding slot for the prompt's full compute — each `step()` packs a
fixed token budget with (a) one in-jit decode chunk over all
decode-phase slots and (b) one prefill chunk of at most `chunk_prefill`
prompt tokens per mid-prompt slot (scheduler.py::plan_step decides the
split; decode is floored at one step, prefills at one token). Admission
binds a slot and reserves pages without running any prompt tokens; the
chunk dispatches then resume the prompt cursor from its page-table
pages, attending over previously-written pages exactly like a prefix
hit, and the final chunk samples the first token and arms decode state
on device. The decode chunk is dispatched before the chunks and synced
after them, so chunk compute overlaps the decode wait — long prompts
cost decoding slots at most one bounded chunk of interference per
iteration instead of a whole prompt.

Sampling is schedule-invariant: every drawn token's key is derived as
`fold_in(fold_in(base_key, uid), token_index)` (sample_tokens_indexed),
a pure function of the request and the token position — never of how
many dispatches the host happened to cut the work into. One-shot,
chunked-prefill and drain-trimmed schedules are therefore
token-identical at ANY temperature, not just greedy
(tests/test_serve.py::test_chunked_schedule_token_identical_temp).

Multi-codebook archs (musicgen: ``cfg.n_codebooks = K > 1``) run
through the SAME engine and schedules: a token is a [K] plane vector
([S, K] prompts, [B, K] decode state, K-tuple host records), embeddings
sum the K planes and the K heads emit [B, K, V] logits inside the same
dispatches — the KV/paged cache is post-embedding, so page tables,
prefix chains and write masks are reused unchanged. EOS early-stop is
defined per-row on codebook 0 (disable via eos_id=None); token stats
count plane tokens (K per position).

With a mesh, every jitted step (prefill, insert, decode) carries
explicit NamedShardings: parameters and the per-slot cache are resolved
from their logical axes via `launch/steps.py::serve_shardings` (the same
rule-table machinery the dry-run and train paths use), so
`--model-parallel N` shards the serving datapath instead of silently
replicating it. Slot-state vectors and token blocks stay replicated —
the slot dim is host-addressed (see `parallel/partition.py::serve_rules`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as steps_mod
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.parallel import partition as part

from .paging import PagePool, SlotPages
from .scheduler import (Completion, Request, SlotRun, TokenBudgetScheduler,
                        bucket_len)
from .spans import SpanRecorder


def sample_tokens(key, logits, temperature):
    """Per-row sampling: temperature <= 0 -> greedy. logits [B, ..., V],
    temperature [B] f32 (broadcast over inner dims, e.g. codebooks).
    Returns int32 [B, ...]. The single sampling implementation for both
    the engine and the lockstep benchmark reference (launch/serve.py's
    `_serve_batch_python`, off the serving hot path)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = temperature.reshape(temperature.shape + (1,) * (greedy.ndim - 1))
    scaled = logits / jnp.maximum(t, 1e-6)[..., None]
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(t > 0.0, sampled, greedy)


def sample_tokens_indexed(base_key, uid, index, logits, temperature):
    """Schedule-invariant per-row sampling: row i draws with the key
    `fold_in(fold_in(base_key, uid[i]), index[i])` — a pure function of
    the request identity and the token position, independent of how the
    host batched dispatches. temperature <= 0 -> greedy. logits
    [B, ..., V], uid/index int32 [B], temperature [B] f32. Returns
    int32 [B, ...]. Inner dims (e.g. [B, K, V] codebook planes) draw
    i.i.d. under the row's single (uid, index) key — still a pure
    function of request identity and token position, so K > 1 streams
    stay schedule-invariant too."""
    keys = jax.vmap(
        lambda u, i: jax.random.fold_in(jax.random.fold_in(base_key, u), i)
    )(uid, index)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = temperature.reshape(temperature.shape + (1,) * (greedy.ndim - 1))
    scaled = logits / jnp.maximum(t, 1e-6)[..., None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(t > 0.0, sampled, greedy)


def make_prefill_sample(cfg: ModelConfig, capacity: int):
    """Jit-able admission step: ragged prefill + on-device first-token
    sampling in one dispatch. (params, batch{tokens [N,S], lengths [N]},
    uids [N], key, temperature [N]) -> (tok0 [N], per-slot cache). The
    first token is token index 0 of its request, so it samples with the
    schedule-invariant (uid, 0) key. Full-vocab logits never leave the
    device — the host syncs only tok0."""
    prefill = steps_mod.make_prefill_step(cfg, capacity=capacity)

    def prefill_sample(params, batch, uids, key, temperature):
        logits, cache = prefill(params, batch)
        idx0 = jnp.zeros_like(uids)
        return sample_tokens_indexed(key, uids, idx0, logits,
                                     temperature), cache

    return prefill_sample


def make_slot_insert(cfg: ModelConfig):
    """Jit-able batched slot admission: scatter N prefilled requests (an
    N-row per-slot cache) into rows `slots` [N] of the big cache + the
    slot-state arrays. `slots` is traced, so one compilation per batch
    size N covers every placement of that many rows."""

    def insert(cache, state, slots, small_cache, slot_vals):
        layers = jax.tree.map(
            lambda big, sm: big.at[:, slots].set(sm.astype(big.dtype)),
            cache["layers"], small_cache["layers"])
        new_cache = {"layers": layers,
                     "cur": cache["cur"].at[slots].set(small_cache["cur"])}
        if "k_pos" in cache:
            new_cache["k_pos"] = cache["k_pos"].at[slots].set(
                small_cache["k_pos"])
        new_state = dict(state)
        for name, val in slot_vals.items():
            new_state[name] = state[name].at[slots].set(
                val.astype(state[name].dtype))
        return new_cache, new_state

    return insert


def make_paged_insert(cfg: ModelConfig, page_size: int):
    """Jit-able batched admission for the paged cache contract: reshape
    each admitted row's k/v into pages and scatter them into the shared
    pool at `write_rows` [N, n_w] (physical page ids; trash-padded rows
    write harmlessly into page 0), install the rows' page tables
    `tbl_rows` [N, pages_per_slot] and per-slot vectors at `slots` [N].
    On a prefix hit `write_rows` covers only the suffix pages, so shared
    prefix pages are never rewritten."""

    def insert(cache, state, slots, small_cache, slot_vals, tbl_rows,
               write_rows):
        layers = dict(cache["layers"])
        for name in ("k", "v"):
            pool = cache["layers"][name]              # [L, P, ps, KV, hd]
            sm = small_cache["layers"][name]          # [L, N, n_w*ps, KV, hd]
            L, N, Wx = sm.shape[:3]
            pages = sm.astype(pool.dtype).reshape(
                L, N, Wx // page_size, page_size, *sm.shape[3:])
            layers[name] = pool.at[:, write_rows].set(pages)
        for name in small_cache["layers"]:
            if name in ("k", "v"):
                continue                              # conv/ssm stay per-slot
            big = cache["layers"][name]
            layers[name] = big.at[:, slots].set(
                small_cache["layers"][name].astype(big.dtype))
        new_cache = {
            "layers": layers,
            "cur": cache["cur"].at[slots].set(small_cache["cur"]),
            "k_pos": cache["k_pos"].at[slots].set(small_cache["k_pos"]),
            "page_tbl": cache["page_tbl"].at[slots].set(tbl_rows),
        }
        new_state = dict(state)
        for name, val in slot_vals.items():
            new_state[name] = state[name].at[slots].set(
                val.astype(state[name].dtype))
        return new_cache, new_state

    return insert


def make_prefix_prefill_sample(cfg: ModelConfig, n_pre: int, page_size: int,
                               capacity: int):
    """Jit-able prefix-hit admission step: gather the shared `n_pre`-page
    prefix out of the pool, ragged-prefill only the suffixes against it,
    and sample first tokens on device — one dispatch, same contract as
    make_prefill_sample but the batch carries *suffix* tokens/lengths.
    The small cache's k_pos width is `capacity` (the padded ring), and
    small k/v are suffix pages only."""
    engine = steps_mod.make_engine(cfg)
    prefix_len = n_pre * page_size

    def prefix_prefill_sample(params, pool_kv, pages, batch, uids, key,
                              temperature):
        prefix = {}
        for name in ("k", "v"):
            sel = pool_kv[name][:, pages]             # [L, n_pre, ps, KV, hd]
            prefix[name] = sel.reshape(sel.shape[0], prefix_len,
                                       *sel.shape[3:])
        logits, cache = M.prefill_prefix_fn(params, batch, cfg, engine,
                                            prefix, prefix_len, capacity,
                                            page_size)
        idx0 = jnp.zeros_like(uids)
        return sample_tokens_indexed(key, uids, idx0, logits,
                                     temperature), cache

    return prefix_prefill_sample


def make_decode_chunk(cfg: ModelConfig, n_steps: int, paged: bool = False):
    """Jit-able (params, cache, state) -> (cache, state, toks [T, B(, K)]):
    `n_steps` decode steps fully on device. Rows record their sampled
    token while active and 0 afterwards; `emitted`/`active` advance so
    the host can replay termination exactly (EOS or budget). With K > 1
    codebooks each step feeds tokens [B, 1, K] and samples a [B, K]
    plane vector under the row's single (uid, index) key; EOS tests
    codebook 0 (the engine's multi-codebook eos contract).

    With `paged`, `active` doubles as the step's write mask: inactive
    rows leave their cache bit-identical (writes land on the trash
    page, k_pos/cur frozen — model.py). For plain continuous batching
    that is merely hygiene (a dead row's ring is fully overwritten at
    its next insert), but the chunked-prefill schedule decodes while
    some slots are still mid-prefill, and those slots' live page tables
    MUST NOT be scribbled by the shared decode scan."""
    engine = steps_mod.make_engine(cfg)
    K = cfg.n_codebooks

    def decode_chunk(params, cache, state):
        budget, temp, eos = state["budget"], state["temp"], state["eos"]
        base, uid = state["key"], state["uid"]

        def body(carry, _):
            cache, tok, emitted, active = carry
            batch = {"tokens": tok[:, None, :] if K > 1 else tok[:, None]}
            if paged:
                batch["write_mask"] = active
            logits, cache = M.decode_fn(params, batch, cache, cfg, engine)
            # emitted counts tokens already drawn (tok0 = index 0), so
            # this step's token is request-token index `emitted` — the
            # same key no matter how steps are cut into chunks
            nxt = sample_tokens_indexed(base, uid, emitted, logits, temp)
            nxt = jnp.where(active[:, None] if K > 1 else active,
                            nxt, 0)                        # pad idle rows
            emitted = emitted + active.astype(jnp.int32)
            head = nxt[..., 0] if K > 1 else nxt
            active = active & (head != eos) & (emitted < budget)
            return (cache, nxt, emitted, active), nxt

        carry0 = (cache, state["tok"], state["emitted"], state["active"])
        (cache, tok, emitted, active), toks = jax.lax.scan(
            body, carry0, None, length=n_steps)
        new_state = dict(state, tok=tok, emitted=emitted, active=active)
        return cache, new_state, toks

    return decode_chunk


def make_chunk_prefill(cfg: ModelConfig, page_size: int):
    """Jit-able chunked-admission dispatch: advance ONE slot's prefill by
    `clen` prompt tokens (models/model.py::run_stack_prefill_chunk) and,
    on the final chunk, sample the first generated token and arm the
    slot's decode state — all on device, with `slot/pos/clen/first/
    final/budget/eos` traced so one compilation per chunk bucket covers
    every slot, offset and chunk length.

    (params, cache, state, batch{tokens [1, S]}, slot, pos, clen, first,
    final, uid, key, temp [1], budget, eos) -> (cache, state, tok0).
    Non-final chunks return garbage tok0 (logits at a mid-prompt token)
    which the host never syncs; the slot's `active` stays False until
    the final chunk, so interleaved decode chunks leave its pages
    untouched (write-mask) and its row reads as idle. The final chunk's
    first token samples with the schedule-invariant (uid, 0) key —
    identical to what one-shot admission would have drawn. K > 1
    codebooks feed chunk tokens [1, S, K] and arm a [K] first-token
    plane vector; the EOS early-stop tests codebook 0."""
    step = steps_mod.make_prefill_chunk_step(cfg, page_size)
    K = cfg.n_codebooks

    def prefill_chunk(params, cache, state, batch, slot, pos, clen, first,
                      final, uid, key, temp, budget, eos):
        W = cache["k_pos"].shape[1]
        j = jnp.arange(W, dtype=jnp.int32)
        # first chunk: forget the slot's previous occupant. A prefix hit
        # starts at pos = prefix_len with the shared pages' positions
        # (ring order == sequence order: prefix caching excludes sliding
        # windows) already valid; a cold start (pos = 0) resets to -1.
        row = jnp.where(first, jnp.where(j < pos, j, -1),
                        cache["k_pos"][slot])
        pool_kv = {"k": cache["layers"]["k"], "v": cache["layers"]["v"]}
        logits, new_kv, new_row = step(params, batch, pool_kv,
                                       cache["page_tbl"][slot], row,
                                       pos, clen)
        uid1 = jnp.full((1,), uid, jnp.int32)
        tok0 = sample_tokens_indexed(key, uid1, jnp.zeros((1,), jnp.int32),
                                     logits, temp)[0]
        new_cache = dict(cache, layers=dict(cache["layers"], **new_kv),
                         cur=cache["cur"].at[slot].set(pos + clen),
                         k_pos=cache["k_pos"].at[slot].set(new_row))
        new_state = dict(state)

        def arm(name, val):
            old = state[name][slot]
            new_state[name] = state[name].at[slot].set(
                jnp.where(final, val, old).astype(state[name].dtype))

        arm("tok", tok0)
        arm("uid", uid)
        arm("emitted", jnp.int32(1))
        head = tok0[0] if K > 1 else tok0
        arm("active", final & (head != eos) & (budget > 1))
        arm("budget", budget)
        arm("temp", temp[0])
        arm("eos", eos)
        return new_cache, new_state, tok0

    return prefill_chunk


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    slots: int = 4              # decode batch width (fixed)
    max_prompt_len: int = 256
    max_len: int = 512          # prompt + generation bound per request
    chunk: int = 8              # in-jit decode steps per host dispatch
    min_bucket: int = 16        # smallest prefill bucket
    admission: str = "batched"  # "batched": up to len(free_slots) same-
                                # bucket requests per prefill dispatch;
                                # "serial": one request per dispatch (the
                                # PR-2 baseline granularity, kept for
                                # benchmarking)
    trim_drain: bool = True     # cap the final decode chunks at the
                                # largest remaining per-slot budget
                                # instead of always running `chunk`
                                # in-jit steps (costs at most a handful
                                # of extra compiled chunk sizes, saves
                                # the wasted drain steps; False keeps
                                # the untrimmed PR-2/3 behavior)
    cache: str = "paged"        # "paged": shared page pool + per-slot
                                # page tables, admission by free pages
                                # (lazily grown, freed at completion);
                                # "slot": the legacy one-full-ring-per-
                                # slot contract, kept for A/B benching.
                                # Pure-SSM stacks have no KV ring to
                                # page and silently use "slot".
    page_size: int = 16         # tokens per page (paged only)
    n_pages: int | None = None  # physical pool size incl. the trash
                                # page; None = slots * pages_per_slot
                                # + 1, i.e. the slot contract's memory
                                # footprint (equal-memory A/B default)
    prefix_cache: bool = True   # share page-aligned common prompt
                                # prefixes across requests (paged,
                                # attention-only, no sliding window)
    chunk_prefill: int = 0      # > 0: admission streams each prompt in
                                # chunks of at most this many tokens,
                                # interleaved with decode under the
                                # token budget (paged attention-only
                                # archs; others silently keep one-shot
                                # admission, like the paged/SSM
                                # fallback). 0 = one-shot admission.
                                # Chunks are clamped to the padded ring
                                # width. Token-identical to one-shot
                                # admission at any temperature (keys
                                # derive from (uid, token index), not
                                # the dispatch schedule).
    token_budget: int | None = None  # per-iteration token cap for the
                                # chunked schedule: decode steps x
                                # decode slots + prefill chunk tokens.
                                # None = slots * chunk + chunk_prefill
                                # (full decode chunk + one prefill
                                # chunk). Both sides keep a one-unit
                                # liveness floor (scheduler.plan_step).
    seed: int = 0

    def __post_init__(self):
        if self.max_prompt_len >= self.max_len:
            raise ValueError("max_prompt_len must leave room to generate "
                             f"({self.max_prompt_len} >= {self.max_len})")
        if self.slots < 1 or self.chunk < 1:
            # zero slots/chunk would make run() spin without progress
            raise ValueError(f"slots ({self.slots}) and chunk "
                             f"({self.chunk}) must be >= 1")
        if self.admission not in ("batched", "serial"):
            raise ValueError(f"admission must be 'batched' or 'serial', "
                             f"got {self.admission!r}")
        if self.cache not in ("paged", "slot"):
            raise ValueError(f"cache must be 'paged' or 'slot', "
                             f"got {self.cache!r}")
        if self.page_size < 1:
            raise ValueError(f"page_size ({self.page_size}) must be >= 1")
        if self.n_pages is not None and self.n_pages < 2:
            raise ValueError(f"n_pages ({self.n_pages}) must be >= 2 "
                             "(one trash page + one usable page)")
        if self.chunk_prefill < 0:
            raise ValueError(f"chunk_prefill ({self.chunk_prefill}) "
                             "must be >= 0 (0 = one-shot admission)")
        if self.token_budget is not None:
            if self.chunk_prefill == 0:
                raise ValueError("token_budget only shapes the chunked "
                                 "schedule; set chunk_prefill > 0")
            if self.token_budget < 1:
                raise ValueError(f"token_budget ({self.token_budget}) "
                                 "must be >= 1")


@dataclasses.dataclass
class EngineStats:
    """Cumulative engine counters. TOKEN COUNTERS COUNT PLANE TOKENS:
    a multi-codebook engine (K > 1) counts K per sequence position —
    what the embedding actually summed and the K heads actually emitted
    — so tok/s rates are comparable across K=1 and K>1 workloads (one
    musicgen position is K plane tokens, not one).

    The timers are sums of the engine's spans (``ServeEngine.spans``):
    ``decode_s``, ``prefill_s`` and ``insert_s`` each add up the
    ``engine.dispatch`` and ``engine.wait`` spans of their kind,
    ``wait_s`` every ``engine.wait``, and ``step_s`` the ``engine.step``
    spans of the steps that did work. Under chunked prefill a chunk is
    never synced on its own: ``prefill_s`` is the chunks' enqueue time
    alone, their compute runs while the host waits for the next decode
    chunk, so ``decode_s`` holds the previous step's prefill compute, and
    a final chunk's first-token wait counts in ``wait_s`` only."""
    prefill_s: float = 0.0
    prefill_tokens: int = 0        # real prompt tokens prefilled
    prefill_padded_tokens: int = 0  # incl. bucket padding
    prefill_batches: int = 0       # admission dispatches
    prefill_requests: int = 0      # requests admitted across dispatches
    insert_s: float = 0.0          # slot-insert dispatch + sync (the
                                   # other half of one-shot admission)
    prefill_chunks: int = 0        # chunked admission: prefill chunk
                                   # dispatches
    decode_s: float = 0.0
    decode_chunks: int = 0
    decode_steps: int = 0          # sum of per-chunk in-jit steps
    decode_tokens: int = 0         # real tokens emitted during decode
    pages_in_use: int = 0          # paged only: live (ref > 0) pool pages now
    pages_peak: int = 0            # paged only: high-water mark of the above
    prefix_hit_tokens: int = 0     # prompt tokens admitted straight from
                                   # cached prefix pages (never prefilled)
    step_s: float = 0.0            # step() calls that did work
    wait_s: float = 0.0            # host blocked on the device
    # live-occupancy gauges: filled by ServeEngine.snapshot() (a
    # point-in-time copy), NOT maintained on the engine's own cumulative
    # `stats` object — they describe "now", not "since boot". The
    # router's dispatch cost and the autoscaler read these.
    slots_in_use: int = 0          # bound decode slots right now
    queue_depth: int = 0           # requests waiting in the engine queue
    pages_free: int = 0            # PagePool.available() (0 = slot cache)

    def delta(self, prev: "EngineStats") -> "EngineStats":
        """Interval view of the stats: cumulative counters become
        (self - prev), gauges keep self's current value. Feeding
        consecutive ServeEngine.snapshot()s through this (or a
        StatsWindow) gives rates over a window instead of since-boot
        totals — the derived *_per_s / utilization properties then
        describe just that window."""
        out = EngineStats()
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name not in _STAT_GAUGES:
                v = v - getattr(prev, f.name)
            setattr(out, f.name, v)
        return out

    def decode_utilization(self, slots: int, planes: int = 1) -> float:
        """Fraction of decode step-slots that emitted a real token
        (decode_tokens / (decode_steps * slots * planes)). Deterministic
        — a function of the schedule, not of wall-clock — which is what
        lets the autoscaler's decisions (and CI's gate on its replica
        trajectory) be reproducible. 0.0 when no decode steps ran.
        `planes` is the engine's codebook count K: decode_tokens counts
        plane tokens, so each occupied step-slot contributes K."""
        denom = self.decode_steps * slots * planes
        return self.decode_tokens / denom if denom else 0.0

    @property
    def prefill_tokens_per_s(self):
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0

    @property
    def admission_tokens_per_s(self):
        """Honest admission throughput: *computed* prompt tokens over the
        WHOLE admission path (ragged prefill + batched slot insert).
        Prefix-hit tokens are excluded — they cost no prefill compute."""
        denom = self.prefill_s + self.insert_s
        return self.prefill_tokens / denom if denom else 0.0

    @property
    def admitted_tokens_per_s(self):
        """Admission throughput as the client sees it: ALL admitted
        prompt tokens (computed + prefix hits) over the admission path.
        With prefix caching this exceeds admission_tokens_per_s by
        exactly the hit tokens' worth of skipped prefill."""
        denom = self.prefill_s + self.insert_s
        return ((self.prefill_tokens + self.prefix_hit_tokens) / denom
                if denom else 0.0)

    @property
    def prefix_hit_rate(self):
        """Fraction of admitted prompt tokens served from cached pages."""
        total = self.prefill_tokens + self.prefix_hit_tokens
        return self.prefix_hit_tokens / total if total else 0.0

    @property
    def decode_tokens_per_s(self):
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0


# gauges describe "now" and are copied (not differenced) by delta()
_STAT_GAUGES = frozenset({
    "slots_in_use", "queue_depth", "pages_free",
    "pages_in_use", "pages_peak",
})

# the EngineStats timer fed by the dispatch and wait spans of each kind;
# the chunked schedule's first-token waits ("first_token") feed wait_s only
_KIND_TIMERS = {"decode": "decode_s", "prefill": "prefill_s",
                "insert": "insert_s"}


class StatsWindow:
    """Rolling interval reader over EngineStats snapshots: each tick()
    returns the delta since the previous tick (first tick: since boot).
    One per replica is how the autoscaler turns cumulative engine
    counters into per-window rates."""

    def __init__(self):
        self._prev = EngineStats()

    def tick(self, snap: EngineStats) -> EngineStats:
        delta = snap.delta(self._prev)
        self._prev = snap
        return delta


class ServeEngine:
    """Continuous-batching server over one model + parameter set.

    >>> eng = ServeEngine(cfg, params, EngineConfig(slots=4))
    >>> eng.submit([1, 2, 3], max_new=16)
    >>> done = eng.run()          # list[Completion], uid order

    With ``mesh`` (and optionally ``rules``) the whole serving datapath —
    prefill+sample, slot insert, decode chunks — runs under explicit
    NamedShardings resolved from the model's logical axes, and the
    parameters/cache are placed onto the mesh at construction. Output is
    token-identical to single-device serving (greedy; verified in
    tests/test_serve_tp.py on a forced multi-device host).
    """

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig = None,
                 *, mesh=None, rules: dict | None = None):
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        # K > 1 (multi-codebook, e.g. musicgen): every token is a [K]
        # plane vector. Prompts are [S, K], host token records are
        # K-tuples, decode threads [B, K] through the same schedules;
        # the cache is post-embedding so nothing page- or slot-shaped
        # changes. EOS is defined on codebook 0 (eos_id=None disables).
        self.K = cfg.n_codebooks
        self.capacity = M.cache_capacity(cfg, self.ecfg.max_len)
        # SSM/conv state is contaminated by trailing pad tokens, so
        # stateful archs prefill at exact prompt lengths (scheduler.py)
        self._exact_buckets = cfg.use_mamba or cfg.parallel_mamba
        # paged contract needs a KV ring; pure-SSM stacks fall back to
        # the slot contract (their whole state is O(1) per row anyway)
        self.paged = (self.ecfg.cache == "paged"
                      and (cfg.has_attention or cfg.parallel_mamba))
        # prefix pages replay cached k/v verbatim; SSM state depends on
        # the full history (can't skip) and sliding-window rings are not
        # in sequence order, so both opt out
        self.prefix_enabled = (self.paged and self.ecfg.prefix_cache
                               and cfg.sliding_window is None
                               and not (cfg.use_mamba or cfg.parallel_mamba))
        # chunked prefill resumes a prompt from pages mid-stream, which
        # needs a paged KV ring and no SSM/conv state (those depend on
        # every earlier token each dispatch); other archs silently keep
        # one-shot admission, mirroring the paged/SSM fallback above
        self.chunked = (self.ecfg.chunk_prefill > 0 and self.paged
                        and not (cfg.use_mamba or cfg.parallel_mamba))

        B = self.ecfg.slots
        self.mesh = mesh
        self.rules = part.serve_rules(rules) if mesh is not None else None
        if self.paged:
            ps = self.ecfg.page_size
            self._n_per_slot = M.pages_per_slot(cfg, self.ecfg.max_len, ps)
            self._w_pad = self._n_per_slot * ps       # padded ring width
            n_pages = self.ecfg.n_pages
            if n_pages is None:
                n_pages = B * self._n_per_slot + 1    # slot-contract memory
            if n_pages - 1 < self._n_per_slot:
                raise ValueError(
                    f"n_pages={n_pages} cannot hold one worst-case request "
                    f"({self._n_per_slot} pages + the trash page): the "
                    "queue head could never be admitted")
            self._n_pages = n_pages
            self._pool = PagePool(n_pages, ps)
            # host mirror of the device page table; rows start at trash.
            # The mirror is authoritative — device copy is refreshed
            # lazily (one upload before a chunk, no extra dispatches)
            self._tbl = np.zeros((B, self._n_per_slot), np.int32)
            self._tbl_dirty = False
            self._slot_pages: dict[int, SlotPages] = {}
            cache = M.init_paged_cache(cfg, B, n_pages, ps, self.ecfg.max_len)
            prefill_capacity = self._w_pad
        else:
            cache = M.init_cache(cfg, B, self.ecfg.max_len, per_slot=True)
            prefill_capacity = self.capacity
        tok_shape = (B, self.K) if self.K > 1 else (B,)
        state = {
            "tok": jnp.zeros(tok_shape, jnp.int32),
            "key": jax.random.key(self.ecfg.seed),   # base key, never split
            "uid": jnp.zeros((B,), jnp.int32),
            "emitted": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), bool),
            "budget": jnp.zeros((B,), jnp.int32),
            "temp": jnp.zeros((B,), jnp.float32),
            "eos": jnp.full((B,), -1, jnp.int32),
        }
        # the SAME base key feeds every sampling site (admission paths
        # pass it explicitly, decode reads state["key"]): token keys are
        # fold_in(fold_in(base, uid), index), so any schedule draws the
        # same tokens for the same requests
        self._base_key = jax.random.key(self.ecfg.seed)

        prefill = make_prefill_sample(cfg, prefill_capacity)
        insert = (make_paged_insert(cfg, self.ecfg.page_size) if self.paged
                  else make_slot_insert(cfg))

        self._decode_fns: dict = {}    # in-jit step count -> jitted chunk
        self._prefix_fns: dict = {}    # (n_pre, suffix bucket) -> jitted fn
        self._chunk_fns: dict = {}     # chunk bucket -> jitted chunk prefill
        if self.chunked:
            # a chunk wider than the padded ring would collide with its
            # own scatter (two chunk tokens sharing a ring slot)
            self._chunk_tokens = min(self.ecfg.chunk_prefill, self._w_pad)
            self._token_budget = (self.ecfg.token_budget
                                  or B * self.ecfg.chunk + self._chunk_tokens)
        if mesh is None:
            self._shardings = None
            self._small_csh = None
            self.params, self.cache, self.state = params, cache, state
            self._prefill = jax.jit(prefill)
            self._insert = jax.jit(insert, donate_argnums=(0, 1))
        else:
            if self.paged:
                psh, csh, repl = steps_mod.serve_shardings(
                    cfg, B, self.ecfg.max_len, mesh, self.rules,
                    page_size=self.ecfg.page_size, n_pages=self._n_pages)
                # admission's small cache keeps the per-slot layout
                # (k/v [L, N, W, KV, hd]); shard it by the per-slot axes
                small_csh = steps_mod.axes_shardings(
                    M.cache_axes(cfg, per_slot=True),
                    M.cache_spec(cfg, B, self.ecfg.max_len, per_slot=True),
                    mesh, self.rules)
            else:
                psh, csh, repl = steps_mod.serve_shardings(
                    cfg, B, self.ecfg.max_len, mesh, self.rules)
                small_csh = csh
            ssh = {name: repl for name in state}
            vsh = {name: repl for name in
                   ("tok", "uid", "emitted", "active", "budget", "temp",
                    "eos")}
            self._shardings = (psh, csh, ssh, repl)
            self._small_csh = small_csh
            self.params = jax.device_put(params, psh)
            self.cache = jax.device_put(cache, csh)
            self.state = jax.device_put(state, ssh)
            self._prefill = jax.jit(
                self._under_rules(prefill),
                in_shardings=(psh, {"tokens": repl, "lengths": repl},
                              repl, repl, repl),
                out_shardings=(repl, small_csh))
            if self.paged:
                self._insert = jax.jit(
                    self._under_rules(insert),
                    in_shardings=(csh, ssh, repl, small_csh, vsh, repl, repl),
                    out_shardings=(csh, ssh), donate_argnums=(0, 1))
            else:
                self._insert = jax.jit(
                    self._under_rules(insert),
                    in_shardings=(csh, ssh, repl, small_csh, vsh),
                    out_shardings=(csh, ssh), donate_argnums=(0, 1))
        self._decode_at(self.ecfg.chunk)     # seed the cache per config

        self.sched = TokenBudgetScheduler(B)
        self.stats = EngineStats()
        self.completions: list[Completion] = []
        self._uid = 0
        self._rec = SpanRecorder()
        self.spans = self._rec.spans   # bounded: the newest SPAN_CAP spans
        self._n_step = 0

    def _decode_at(self, n_steps: int):
        """The jitted decode chunk running ``n_steps`` in-jit steps,
        built (and cached) on demand; jit compilation itself stays lazy
        (first call per size). Drain trimming adds at most a handful of
        sizes beyond ``ecfg.chunk`` per engine lifetime (one per
        distinct final remaining-budget value — typically one)."""
        fn = self._decode_fns.get(n_steps)
        if fn is None:
            decode = make_decode_chunk(self.cfg, n_steps, paged=self.paged)
            if self._shardings is None:
                fn = jax.jit(decode, donate_argnums=(1, 2))
            else:
                psh, csh, ssh, repl = self._shardings
                fn = jax.jit(
                    self._under_rules(decode),
                    in_shardings=(psh, csh, ssh),
                    out_shardings=(csh, ssh, repl), donate_argnums=(1, 2))
            self._decode_fns[n_steps] = fn
        return fn

    def _prefix_prefill_at(self, n_pre: int, sbucket: int):
        """The jitted prefix-hit admission step for an `n_pre`-page
        shared prefix and a `sbucket`-padded suffix block, built on
        demand (one trace per (n_pre, sbucket) pair)."""
        key = (n_pre, sbucket)
        fn = self._prefix_fns.get(key)
        if fn is None:
            raw = make_prefix_prefill_sample(
                self.cfg, n_pre, self.ecfg.page_size, self._w_pad)
            if self._shardings is None:
                fn = jax.jit(raw)
            else:
                psh, csh, ssh, repl = self._shardings
                pool_sh = {"k": csh["layers"]["k"], "v": csh["layers"]["v"]}
                fn = jax.jit(
                    self._under_rules(raw),
                    in_shardings=(psh, pool_sh, repl,
                                  {"tokens": repl, "lengths": repl},
                                  repl, repl, repl),
                    out_shardings=(repl, self._small_csh))
            self._prefix_fns[key] = fn
        return fn

    def _chunk_at(self, sbucket: int):
        """The jitted chunk-prefill dispatch for a `sbucket`-padded
        chunk, built on demand — slot, offset, length and the final-
        chunk flags are all traced, so log2(chunk_prefill) traces cover
        the whole chunked admission path."""
        fn = self._chunk_fns.get(sbucket)
        if fn is None:
            raw = make_chunk_prefill(self.cfg, self.ecfg.page_size)
            if self._shardings is None:
                fn = jax.jit(raw, donate_argnums=(1, 2))
            else:
                psh, csh, ssh, repl = self._shardings
                fn = jax.jit(
                    self._under_rules(raw),
                    in_shardings=(psh, csh, ssh, {"tokens": repl},
                                  repl, repl, repl, repl, repl, repl,
                                  repl, repl, repl, repl),
                    out_shardings=(csh, ssh, repl), donate_argnums=(1, 2))
            self._chunk_fns[sbucket] = fn
        return fn

    def _under_rules(self, fn):
        """Trace `fn` under this engine's (mesh, rules) context so the
        model's logical_constraint annotations resolve; the context
        manager only runs at trace time, cached calls skip it."""
        mesh, rules = self.mesh, self.rules

        @functools.wraps(fn)            # the program keeps fn's name
        def traced(*args):
            with part.axis_rules(mesh, rules):
                return fn(*args)

        return traced

    # -- spans and the timers they feed ------------------------------------

    @contextlib.contextmanager
    def _device(self, name: str, kind: str, **attrs):
        """An ``engine.dispatch`` or ``engine.wait`` span of one kind of
        work. Its duration feeds that kind's timer (decode, prefill,
        insert) and, for a wait, ``wait_s``."""
        with self._rec.span(name, kind=kind, **attrs) as sp:
            yield sp
        dt = sp.t1 - sp.t0
        timer = _KIND_TIMERS.get(kind)
        if timer is not None:
            setattr(self.stats, timer, getattr(self.stats, timer) + dt)
        if name == "engine.wait":
            self.stats.wait_s += dt

    def _dispatch(self, kind: str, **attrs):
        return self._device("engine.dispatch", kind, **attrs)

    def _wait(self, kind: str, **attrs):
        return self._device("engine.wait", kind, **attrs)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt_tokens, max_new: int, *, temperature: float = 0.0,
               eos_id: Optional[int] = None, uid: Optional[int] = None,
               arrival_s: Optional[float] = None) -> int:
        """Queue one request; returns its uid. A router passes `uid`
        (its own global id — sampling keys fold it in, so placement
        does not change the stream) and `arrival_s` (when the request
        entered the router, so Completion.queue_s spans the real wait,
        router queue included). Uniqueness of a forced uid is the
        caller's contract; the internal counter skips past it.

        Multi-codebook engines (K > 1) take prompts [S, K] — an array
        or a list of K-tuples — and record every host-side token as a
        K-tuple; lengths, buckets and page costs stay positional."""
        arr = np.asarray(prompt_tokens)
        if self.K > 1:
            if arr.ndim != 2 or arr.shape[-1] != self.K:
                raise ValueError(
                    f"multi-codebook prompts must be [S, {self.K}], got "
                    f"shape {arr.shape}")
            toks = [tuple(int(x) for x in row) for row in arr]
        else:
            toks = [int(t) for t in arr.reshape(-1)]
        if not toks:
            raise ValueError("empty prompt")
        if len(toks) > self.ecfg.max_prompt_len:
            raise ValueError(f"prompt length {len(toks)} > max_prompt_len "
                             f"{self.ecfg.max_prompt_len}")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if uid is None:
            uid = self._uid
            self._uid += 1
        else:
            uid = int(uid)
            self._uid = max(self._uid, uid + 1)
        now = time.perf_counter()
        self.sched.submit(Request(
            uid=uid, tokens=toks, max_new=max_new, temperature=temperature,
            eos_id=-1 if eos_id is None else int(eos_id),
            submitted_at=now,
            arrival_s=now if arrival_s is None else float(arrival_s)))
        return uid

    def snapshot(self) -> EngineStats:
        """Point-in-time copy of the cumulative stats with the
        live-occupancy gauges filled (slots_in_use / queue_depth /
        pages_free). Pair consecutive snapshots via EngineStats.delta
        (or a StatsWindow) for windowed rates."""
        s = dataclasses.replace(self.stats)
        s.slots_in_use = len(self.sched.active_slots())
        s.queue_depth = len(self.sched.queue)
        s.pages_free = self._pool.available() if self.paged else 0
        return s

    # -- admission ---------------------------------------------------------

    def _bucket_of(self, length: int) -> int:
        return bucket_len(length, min_bucket=self.ecfg.min_bucket,
                          max_len=self.ecfg.max_prompt_len,
                          exact=self._exact_buckets)

    def _chunk_bucket(self, length: int) -> int:
        """Padded chunk length (chunked archs are never exact-bucketed:
        the SSM gate on `chunked` implies pow2 buckets are safe)."""
        return bucket_len(
            length, min_bucket=min(self.ecfg.min_bucket, self._chunk_tokens),
            max_len=self._chunk_tokens)

    def _head(self, tok) -> int:
        """Codebook-0 id of one sampled token (scalar, or a [K] plane
        row) — the plane the multi-codebook EOS contract tests."""
        return int(tok[0]) if self.K > 1 else int(tok)

    def _as_token(self, tok):
        """One sampled token as its host-side record: an int, or a
        K-tuple of plane ids (hashable, so prefix chains key on it)."""
        return tuple(int(x) for x in tok) if self.K > 1 else int(tok)

    def _match_of(self, req: Request) -> list:
        """Cached prefix page chain for a request (possibly empty),
        capped so the suffix is never empty — the admission step needs
        at least one real token to read first-token logits from."""
        if not self.prefix_enabled:
            return []
        limit = (len(req.tokens) - 1) // self.ecfg.page_size
        return self._pool.match(req.tokens, limit=limit)

    def _admit_key(self, req: Request):
        """Requests admitted in one ragged dispatch must agree on both
        the (suffix) prefill bucket and the matched prefix chain."""
        match = self._match_of(req)
        sbucket = self._bucket_of(
            len(req.tokens) - len(match) * self.ecfg.page_size)
        return (sbucket, tuple(match))

    def _page_cost(self, req: Request) -> int:
        """Worst-case NEW pages this request could ever need (prompt plus
        full generation budget, minus its cached prefix). Admitting by
        this bound is what lets growth draw on reservations instead of
        failing mid-decode."""
        ps = self.ecfg.page_size
        L = len(req.tokens)
        gen = min(req.max_new, self.ecfg.max_len - L)
        worst = min(-(-(L + gen) // ps), self._n_per_slot)
        return max(worst - len(self._match_of(req)), 0)

    def _reserve_pages(self, reqs: list):
        """Pin each request's matched prefix, allocate its prompt pages
        and reserve its worst-case growth, in queue order. A request
        that no longer fits (the evictable pool shrank since the batch
        was sized) rolls back and returns to the queue front along with
        everything behind it. Returns (admitted requests, their plans)."""
        ps = self.ecfg.page_size
        taken, plans = [], []
        for i, req in enumerate(reqs):
            match = self._match_of(req)
            if match:
                # pin before any alloc below could evict the chain
                self._pool.share(match)
            L = len(req.tokens)
            gen = min(req.max_new, self.ecfg.max_len - L)
            n_now = min(-(-L // ps), self._n_per_slot)   # prompt pages
            worst = min(-(-(L + gen) // ps), self._n_per_slot)
            new = self._pool.alloc(n_now - len(match))
            ok = new is not None and self._pool.reserve(worst - n_now)
            if not ok:
                if new is not None:
                    self._pool.release(new)
                if match:
                    self._pool.release(match)
                self.sched.queue.extendleft(reversed(reqs[i:]))
                break
            taken.append(req)
            plans.append(SlotPages(pages=match + new,
                                    n_shared=len(match), worst=worst))
        return taken, plans

    def _release_plan(self, sp: SlotPages) -> None:
        self._pool.release(sp.pages)
        self._pool.unreserve(sp.worst - len(sp.pages))

    def _admit_chunked(self, slots: list, reqs: list) -> bool:
        """Chunked admission: reserve pages and bind the slot, but run
        ZERO prompt tokens — the prefill cursor starts past any prefix
        hit and `_step_chunked` advances it one budgeted chunk per
        iteration. No dispatch happens here, so (unlike one-shot
        `_admit`) requests in one round need not share an admission
        key."""
        reqs, plans = self._reserve_pages(reqs)
        if not reqs:
            return False
        self.stats.pages_in_use = self._pool.in_use
        self.stats.pages_peak = self._pool.pages_peak
        ps = self.ecfg.page_size
        now = time.perf_counter()
        for b, req, sp in zip(slots, reqs, plans):
            sp.prefill_pos = sp.n_shared * ps
            sp.prefill_done = False
            sp.first_chunk = True
            self._tbl[b, :len(sp.pages)] = sp.pages
            self._tbl[b, len(sp.pages):] = 0
            self._tbl_dirty = True
            self.stats.prefix_hit_tokens += sp.n_shared * ps * self.K
            self.stats.prefill_requests += 1
            self.sched.bind(b, SlotRun(request=req, tokens=[],
                                       admitted_at=now))
            self._slot_pages[b] = sp
        return True

    def _admit(self, slots: list, reqs: list) -> bool:
        """Admit `reqs` (same admission key) into free rows `slots[:N]`:
        one ragged prefill dispatch with on-device first-token sampling
        (prefix hits prefill only the suffix against the cached pages),
        one multi-row insert. Only the [N] tok0 vector is synced.
        Returns False when nothing could be admitted (page exhaustion:
        the caller stops admitting until decode frees pages)."""
        plans = None
        if self.paged:
            reqs, plans = self._reserve_pages(reqs)
            if not reqs:
                return False
            self.stats.pages_in_use = self._pool.in_use
            self.stats.pages_peak = self._pool.pages_peak
        N = len(reqs)
        ps = self.ecfg.page_size
        n_pre = plans[0].n_shared if plans else 0
        pre_len = n_pre * ps
        lens = [len(r.tokens) - pre_len for r in reqs]     # suffix lengths
        bucket = self._bucket_of(lens[0])
        shape = (N, bucket, self.K) if self.K > 1 else (N, bucket)
        padded = np.zeros(shape, np.int32)
        for i, r in enumerate(reqs):
            padded[i, :lens[i]] = np.asarray(r.tokens[pre_len:], np.int32)
        batch = {"tokens": jnp.asarray(padded),
                 "lengths": jnp.asarray(lens, jnp.int32)}
        uids = jnp.asarray([r.uid for r in reqs], jnp.int32)
        temps = jnp.asarray([r.temperature for r in reqs], jnp.float32)

        program = "prefix_prefill_sample" if n_pre else "prefill_sample"
        with self._dispatch("prefill", program=program, requests=N,
                            tokens=sum(lens), bucket=bucket):
            if n_pre:
                pool_kv = {"k": self.cache["layers"]["k"],
                           "v": self.cache["layers"]["v"]}
                pages = jnp.asarray(plans[0].pages[:n_pre], jnp.int32)
                tok0, small_cache = self._prefix_prefill_at(n_pre, bucket)(
                    self.params, pool_kv, pages, batch, uids,
                    self._base_key, temps)
            else:
                tok0, small_cache = self._prefill(self.params, batch, uids,
                                                  self._base_key, temps)
        with self._wait("prefill") as w:
            tok0 = np.asarray(tok0)                    # [N(, K)] ints
        now = w.t1
        # token stats count PLANE tokens (positions x K): what the model
        # actually embedded/emitted, so K=1 and K>1 rates are comparable
        self.stats.prefill_tokens += sum(lens) * self.K
        self.stats.prefix_hit_tokens += N * pre_len * self.K
        self.stats.prefill_padded_tokens += N * bucket * self.K
        self.stats.prefill_batches += 1
        self.stats.prefill_requests += N

        budgets = [min(r.max_new, self.ecfg.max_len - len(r.tokens))
                   for r in reqs]
        # single-token requests finish at admission and never occupy a
        # slot's scheduler binding; when the batch has survivors their
        # dead rows still ride the one batched insert (active=False,
        # page-table row all-trash) and are fully overwritten by the
        # row's next occupant, so nothing can leak — an all-dead batch
        # skips the insert entirely
        live = np.ones(N, bool)
        for i, (req, t, budget) in enumerate(zip(reqs, tok0, budgets)):
            if self._head(t) == req.eos_id or budget <= 1:
                reason = "eos" if self._head(t) == req.eos_id else "length"
                self._complete(req, [self._as_token(t)], reason,
                               admitted_at=now, token_times=[now])
                live[i] = False
                if plans:
                    self._release_plan(plans[i])

        if not live.any():
            if self.paged:
                self.stats.pages_in_use = self._pool.in_use
            return True                 # requests completed: progress
        slot_vals = {
            "tok": jnp.asarray(tok0.astype(np.int32)),
            "uid": uids,
            "emitted": jnp.ones((N,), jnp.int32),
            "active": jnp.asarray(live),
            "budget": jnp.asarray(budgets, jnp.int32),
            "temp": temps,
            "eos": jnp.asarray([r.eos_id for r in reqs], jnp.int32),
        }
        insert_args = [self.cache, self.state,
                       jnp.asarray(slots[:N], jnp.int32), small_cache,
                       slot_vals]
        if self.paged:
            # logical -> physical rows for the insert: the full table
            # per row (unallocated tail maps to trash) plus the pages
            # the small cache actually writes — the whole padded ring
            # when cold, only the suffix pages on a prefix hit (shared
            # prefix pages are never rewritten)
            tbl_rows = np.zeros((N, self._n_per_slot), np.int32)
            n_w = self._n_per_slot if n_pre == 0 else -(-bucket // ps)
            write_rows = np.zeros((N, n_w), np.int32)
            for i, sp in enumerate(plans):
                if not live[i]:
                    continue
                tbl_rows[i, :len(sp.pages)] = sp.pages
                own = sp.pages[n_pre:]
                write_rows[i, :min(len(own), n_w)] = own[:n_w]
            insert_args += [jnp.asarray(tbl_rows), jnp.asarray(write_rows)]
        with self._dispatch("insert", program="insert", requests=N):
            self.cache, self.state = self._insert(*insert_args)
        # the insert is the other half of admission: sync (any output of
        # the one dispatch) so its cost lands in insert_s instead of
        # being silently attributed to the next decode chunk
        with self._wait("insert"):
            jax.block_until_ready(self.state["tok"])
        if self.paged:
            self._tbl[slots[:N]] = tbl_rows    # mirror == device now
            if self.prefix_enabled:
                # every fully-written prompt page becomes (or extends) a
                # registered chain; duplicate keys keep the first page
                for i, (req, sp) in enumerate(zip(reqs, plans)):
                    if live[i]:
                        n_full = len(req.tokens) // ps
                        self._pool.register(req.tokens[:n_full * ps],
                                            sp.pages[:n_full])
        for i in np.nonzero(live)[0]:
            self.sched.bind(slots[i], SlotRun(
                request=reqs[i], tokens=[self._as_token(tok0[i])],
                admitted_at=now, token_times=[now]))
            if self.paged:
                self._slot_pages[slots[i]] = plans[i]
        return True

    def _admit_ready(self) -> None:
        while True:
            free = self.sched.free_slots()
            if not free or not self.sched.queue:
                return
            # early-completed requests leave their slots free, so the
            # loop re-checks free slots and the (new) queue head's key
            # each round rather than iterating a fixed plan
            width = 1 if self.ecfg.admission == "serial" else len(free)
            if self.chunked:
                # no shared dispatch -> no admission-key constraint
                # (constant key); page budget still gates the batch
                reqs = self.sched.next_batch(
                    width, lambda r: 0, cost_of=self._page_cost,
                    budget=self._pool.available())
                if not reqs or not self._admit_chunked(free, reqs):
                    return
                continue
            if self.paged:
                reqs = self.sched.next_batch(
                    width, self._admit_key, cost_of=self._page_cost,
                    budget=self._pool.available())
            else:
                reqs = self.sched.next_batch(width, self._admit_key)
            if not reqs:
                return
            if not self._admit(free, reqs):
                return

    def _complete(self, req: Request, tokens, reason: str, *,
                  admitted_at: float, token_times=None) -> None:
        tt = list(token_times or ())
        # ttft as the client sees it: from system entry (router front
        # door when routed), not from this engine's submit
        ttft = (tt[0] - (req.arrival_s or req.submitted_at)) if tt else 0.0
        itl = float(np.percentile(np.diff(tt), 99.0)) if len(tt) >= 2 else 0.0
        self.completions.append(Completion(
            uid=req.uid, prompt_len=len(req.tokens), tokens=list(tokens),
            finish_reason=reason, submitted_at=req.submitted_at,
            admitted_at=admitted_at, finished_at=time.perf_counter(),
            arrival_s=req.arrival_s or req.submitted_at,
            ttft_s=ttft, itl_p99_s=itl))

    # -- page lifecycle (paged contract only) ------------------------------

    def _grow_pages(self, active: list, n_steps: int) -> None:
        """Lazily allocate the pages the coming chunk will write into,
        drawn from each slot's admission-time reservation (cannot fail).
        A row that exhausts its budget mid-chunk keeps writing — past
        its last allocated page those writes land on the trash page."""
        ps = self.ecfg.page_size
        for b in active:
            run = self.sched.slots[b]
            sp = self._slot_pages[b]
            L = len(run.request.tokens)
            g = len(run.tokens)                  # generated so far (tok0..)
            # chunk inputs sit at positions L+g-1 .. L+g-2+n_steps
            need = min(-(-(L + g - 1 + n_steps) // ps),
                       self._n_per_slot, sp.worst)
            delta = need - len(sp.pages)
            if delta > 0:
                new = self._pool.alloc_reserved(delta)
                self._tbl[b, len(sp.pages):need] = new
                sp.pages.extend(new)
                self._tbl_dirty = True
        self.stats.pages_in_use = self._pool.in_use
        self.stats.pages_peak = self._pool.pages_peak

    def _free_slot(self, b: int) -> None:
        """Return an evicted slot's pages — decref shared prefix pages,
        park registered ref-0 pages as evictable cache, free the rest —
        and point its table row back at trash."""
        self._release_plan(self._slot_pages.pop(b))
        self._tbl[b] = 0
        self._tbl_dirty = True
        self.stats.pages_in_use = self._pool.in_use

    def _push_tbl(self) -> None:
        """Upload the host page-table mirror if it changed (page growth
        or slot free): one transfer before the chunk, zero dispatches."""
        if not self._tbl_dirty:
            return
        tbl = jnp.asarray(self._tbl)
        if self.mesh is not None:
            tbl = jax.device_put(tbl, self._shardings[3])
        self.cache = dict(self.cache, page_tbl=tbl)
        self._tbl_dirty = False

    # -- decode loop -------------------------------------------------------

    def step(self) -> bool:
        """One engine iteration. Chunked engines pack a token budget
        (decode chunk + one prefill chunk per mid-prompt slot); legacy
        engines run admit-then-decode. Returns False when idle.

        An iteration with anything queued or bound is one
        ``engine.step`` span (attrs: ``step`` index, decode slots
        ``n_slots``, in-jit decode steps ``n_steps``, prefill chunks
        ``n_chunks``), holding ``engine.admit``, ``engine.pages``, one
        ``engine.dispatch`` per program dispatched, one ``engine.wait``
        per host block on the device, and ``engine.harvest``."""
        if not self.sched.pending:
            return False
        with self._rec.span("engine.step", step=self._n_step, n_slots=0,
                            n_steps=0, n_chunks=0) as sp:
            did = (self._step_chunked(sp.attrs) if self.chunked
                   else self._step_phased(sp.attrs))
        self._n_step += 1
        if did:
            self.stats.step_s += sp.t1 - sp.t0
        return did

    def _step_phased(self, attrs: dict) -> bool:
        """Admit, then one decode chunk over every bound slot."""
        with self._rec.span("engine.admit"):
            self._admit_ready()
        active = self.sched.active_slots()
        if not active:
            return False

        n_steps = self.ecfg.chunk
        if self.ecfg.trim_drain:
            # drain cap: when every surviving slot's remaining budget is
            # below the chunk size, run a shorter final chunk instead of
            # paying for in-jit steps that only decode dead rows. The
            # host knows each slot's remaining budget exactly (EOS can
            # only end a row EARLIER, never extend it). Sampling keys
            # derive from (uid, token index), so trimming is
            # token-identical at any temperature.
            need = max(
                min(run.request.max_new,
                    self.ecfg.max_len - len(run.request.tokens))
                - len(run.tokens)
                for run in (self.sched.slots[b] for b in active))
            n_steps = max(1, min(n_steps, need))

        attrs.update(n_slots=len(active), n_steps=n_steps)
        if self.paged:
            with self._rec.span("engine.pages"):
                self._grow_pages(active, n_steps)
                self._push_tbl()
        with self._dispatch("decode", program="decode_chunk",
                            n_steps=n_steps, n_slots=len(active)):
            self.cache, self.state, toks = self._decode_at(n_steps)(
                self.params, self.cache, self.state)
        with self._wait("decode") as w:
            toks = np.asarray(toks)                        # [T, B]
        self.stats.decode_chunks += 1
        self.stats.decode_steps += toks.shape[0]
        with self._rec.span("engine.harvest"):
            self._harvest(active, toks, w.t1)
        return True

    def _harvest(self, active: list, toks, now: float) -> None:
        """Fold one synced decode chunk's tokens [T, B(, K)] into the
        bound runs; evict + complete rows that hit EOS or their budget.
        All T tokens become host-visible at the same sync, so they share
        one timestamp (ITL measures chunk-sync gaps, not per-token
        gaps). decode_tokens counts plane tokens: K per position."""
        for b in active:
            run = self.sched.slots[b]
            req = run.request
            budget = min(req.max_new, self.ecfg.max_len - len(req.tokens))
            for t in range(toks.shape[0]):
                raw = toks[t, b]
                tok = self._head(raw)
                run.tokens.append(self._as_token(raw))
                run.token_times.append(now)
                self.stats.decode_tokens += self.K
                if tok == req.eos_id or len(run.tokens) >= budget:
                    self.sched.evict(b)
                    if self.paged:
                        self._free_slot(b)
                    self._complete(
                        req, run.tokens,
                        "eos" if tok == req.eos_id else "length",
                        admitted_at=run.admitted_at,
                        token_times=run.token_times)
                    break

    def _step_chunked(self, attrs: dict) -> bool:
        """One token-budget iteration: plan decode steps + prefill
        chunks, dispatch the decode chunk FIRST (its sync then never
        waits on chunk compute — chunk dispatches overlap the decode
        wait), run one chunk per mid-prompt slot, sync decode, harvest.

        Final-chunk slots sample their first token on device inside the
        chunk dispatch and flip active there, so they join the NEXT
        iteration's decode chunk with zero extra dispatches."""
        with self._rec.span("engine.admit"):
            self._admit_ready()
        active = self.sched.active_slots()
        if not active:
            return False
        pf = [b for b in active if not self._slot_pages[b].prefill_done]
        pf.sort(key=lambda b: self.sched.slots[b].request.uid)
        dec = [b for b in active if self._slot_pages[b].prefill_done]

        n_steps = self.ecfg.chunk
        if dec and self.ecfg.trim_drain:
            need = max(
                min(run.request.max_new,
                    self.ecfg.max_len - len(run.request.tokens))
                - len(run.tokens)
                for run in (self.sched.slots[b] for b in dec))
            n_steps = max(1, min(n_steps, need))
        plan = self.sched.plan_step(
            budget=self._token_budget, chunk_tokens=self._chunk_tokens,
            decode_steps=n_steps if dec else 0, n_decode=len(dec),
            prefill_left=[
                (b, len(self.sched.slots[b].request.tokens)
                 - self._slot_pages[b].prefill_pos) for b in pf])

        attrs.update(n_slots=len(dec), n_steps=plan.decode_steps,
                     n_chunks=len(plan.chunks))
        with self._rec.span("engine.pages"):
            if dec:
                self._grow_pages(dec, plan.decode_steps)
            self._push_tbl()    # one upload covers decode AND chunks
        toks = None
        if dec:
            with self._dispatch("decode", program="decode_chunk",
                                n_steps=plan.decode_steps, n_slots=len(dec)):
                self.cache, self.state, toks = self._decode_at(
                    plan.decode_steps)(self.params, self.cache, self.state)

        finals = []
        for b, c in plan.chunks:
            run = self.sched.slots[b]
            req = run.request
            sp = self._slot_pages[b]
            pos = sp.prefill_pos
            final = pos + c == len(req.tokens)
            sbucket = self._chunk_bucket(c)
            shape = (1, sbucket, self.K) if self.K > 1 else (1, sbucket)
            padded = np.zeros(shape, np.int32)
            padded[0, :c] = np.asarray(req.tokens[pos:pos + c], np.int32)
            gen = min(req.max_new, self.ecfg.max_len - len(req.tokens))
            # enqueue time only: chunks are never synced here, their
            # compute overlaps the next decode wait
            with self._dispatch("prefill", program="prefill_chunk",
                                uid=req.uid, slot=b, pos=pos, tokens=c,
                                bucket=sbucket):
                self.cache, self.state, tok0 = self._chunk_at(sbucket)(
                    self.params, self.cache, self.state,
                    {"tokens": jnp.asarray(padded)},
                    jnp.int32(b), jnp.int32(pos), jnp.int32(c),
                    jnp.asarray(sp.first_chunk), jnp.asarray(final),
                    jnp.int32(req.uid), self._base_key,
                    jnp.full((1,), req.temperature, jnp.float32),
                    jnp.int32(gen), jnp.int32(req.eos_id))
            self.stats.prefill_chunks += 1
            self.stats.prefill_tokens += c * self.K
            self.stats.prefill_padded_tokens += sbucket * self.K
            sp.prefill_pos = pos + c
            sp.first_chunk = False
            if final:
                sp.prefill_done = True
                finals.append((b, tok0))

        if toks is not None:
            with self._wait("decode") as w:
                toks = np.asarray(toks)                    # [T, B]
            self.stats.decode_chunks += 1
            self.stats.decode_steps += toks.shape[0]
            with self._rec.span("engine.harvest"):
                self._harvest(dec, toks, w.t1)

        for b, tok0 in finals:
            uid = self.sched.slots[b].request.uid
            with self._wait("first_token", uid=uid) as w:
                raw = np.asarray(tok0)
            with self._rec.span("engine.harvest", uid=uid):
                self._harvest_first(b, raw, w.t1)
        return True

    def _harvest_first(self, b: int, raw, now: float) -> None:
        """Record a final chunk's synced first token: register the
        prompt's full pages as a prefix chain, then complete the request
        if that token ends it (EOS, or a budget of one)."""
        run = self.sched.slots[b]
        req = run.request
        sp = self._slot_pages[b]
        if self.prefix_enabled:
            ps = self.ecfg.page_size
            n_full = len(req.tokens) // ps
            self._pool.register(req.tokens[:n_full * ps], sp.pages[:n_full])
        t = self._head(raw)
        run.tokens.append(self._as_token(raw))
        run.token_times.append(now)
        gen = min(req.max_new, self.ecfg.max_len - len(req.tokens))
        if t == req.eos_id or gen <= 1:
            self.sched.evict(b)
            self._free_slot(b)
            self._complete(
                req, run.tokens, "eos" if t == req.eos_id else "length",
                admitted_at=run.admitted_at, token_times=run.token_times)

    def run(self) -> list[Completion]:
        """Serve until queue and slots drain. Completions in uid order."""
        while self.sched.pending:
            if not self.step() and not self.sched.queue:
                break
        return sorted(self.completions, key=lambda c: c.uid)
