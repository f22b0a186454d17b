"""LM assembly: embeddings -> scan(blocks) -> norm -> head(s), plus the
three step functions the launcher lowers: train forward/loss, prefill,
and single-token decode against a KV/SSM cache.

Layer parameters are stacked on a leading "layer" axis and iterated with
`jax.lax.scan` — compile time stays flat in depth (60-layer stacks lower
in <1s) and remat policy applies per block.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.activations import ActivationEngine, init_act_params
from repro.parallel.partition import Boxed, box, is_boxed, unbox_tree
from repro.parallel.partition import logical_constraint as lc

from .config import ModelConfig
from .layers import BlockIO, apply_block, apply_norm, init_block, init_norm, dtype_of


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig):
    """Returns a Boxed(value, logical_axes) tree of all parameters."""
    ks = jax.random.split(key, 4 + cfg.n_layers)
    V, d, K = cfg.padded_vocab, cfg.d_model, cfg.n_codebooks
    embed_shape = (K, V, d) if K > 1 else (V, d)
    embed_axes = ("codebook", "vocab", "embed") if K > 1 else ("vocab", "embed")
    params: dict[str, Any] = {
        "embed": box(embed_axes,
                     jax.random.normal(ks[0], embed_shape, jnp.float32) * 0.02),
        "ln_f": init_norm(ks[1], cfg),
        "lm_head": box(embed_axes[::-1] if K == 1 else ("codebook", "embed", "vocab"),
                       jax.random.normal(ks[2], (K, d, V) if K > 1 else (d, V),
                                         jnp.float32) * (1.0 / np.sqrt(d))),
    }
    layers = [init_block(ks[4 + i], cfg) for i in range(cfg.n_layers)]
    params["blocks"] = jax.tree.map(
        lambda *ls: Boxed(jnp.stack([b.value for b in ls]),
                          ("layer",) + ls[0].axes),
        *layers, is_leaf=is_boxed)
    # approximant params (knots / coefficients) as model leaves: one
    # entry per distinct trainable activation config in the per-layer
    # assignment, replicated (tiny arrays). Frozen unless --train-act.
    act = init_act_params(cfg.layer_activation_configs())
    if act:
        params["act"] = {tag: box((None,) * arr.ndim, jnp.asarray(arr))
                         for tag, arr in act.items()}
    return params


def abstract_params(cfg: ModelConfig, seed: int = 0):
    """(shapes_tree, axes_tree) without allocating anything."""
    side = []

    def f(k):
        vals, axes = unbox_tree(init_lm(k, cfg))
        side.append(axes)
        return vals

    shapes = jax.eval_shape(f, jax.random.key(seed))
    return shapes, side[0]


def materialize_params(cfg: ModelConfig, seed: int = 0):
    """(params, axes) with real arrays (smoke tests / examples)."""
    return unbox_tree(init_lm(jax.random.key(seed), cfg))


# ---------------------------------------------------------------------------
# embeddings & heads
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig, patch_embeds=None):
    cdt = dtype_of(cfg)
    emb = params["embed"].astype(cdt)
    if cfg.n_codebooks > 1:                      # tokens [B, S, K]
        # musicgen-style: per-codebook embeddings summed
        x = sum(emb[k][tokens[..., k]] for k in range(cfg.n_codebooks))
    else:
        x = emb[tokens]
    if cfg.patch_embed_input and patch_embeds is not None:
        x = x + patch_embeds.astype(cdt)
    return lc(x, "batch", "seq", "act_embed")


def lm_logits(params, h, cfg: ModelConfig):
    head = params["lm_head"].astype(jnp.float32)
    hf = h.astype(jnp.float32)
    if cfg.n_codebooks > 1:
        logits = jnp.einsum("bsd,kdv->bskv", hf, head)
    else:
        logits = jnp.einsum("bsd,dv->bsv", hf, head)
    return lc(logits, "batch", "seq", None, "act_vocab") \
        if cfg.n_codebooks > 1 else lc(logits, "batch", "seq", "act_vocab")


# ---------------------------------------------------------------------------
# stack runners
# ---------------------------------------------------------------------------

def _bind_engine(engine, params):
    """Engine(s) with tanh params bound from the model pytree (the
    optional ``params["act"]`` subtree) — resolved once per step
    function at trace time, so the approximant parameters are ordinary
    differentiable leaves wherever the model runs."""
    act = params.get("act") if hasattr(params, "get") else None
    return engine.bind(act) if act else engine


def _scan_layers(engine, body_for, init, xs):
    """Scan the layer stack under a (possibly per-layer) engine.

    ``body_for(eng)`` returns a ``lax.scan`` body closing over ONE
    ActivationEngine. A plain engine scans all layers in a single
    ``lax.scan`` — the exact pre-assignment jaxpr — while a
    ``LayerEngines`` assignment scans each maximal same-engine segment
    separately (stacked params sliced along the layer axis) and
    concatenates the per-layer outputs back together."""
    segs = getattr(engine, "segments", None)
    if segs is None:
        return jax.lax.scan(body_for(engine), init, xs)
    carry, outs = init, []
    for s, t, eng in segs:
        carry, ys = jax.lax.scan(body_for(eng), carry,
                                 jax.tree.map(lambda a: a[s:t], xs))
        outs.append(ys)
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *outs)


def _positions_for(batch, cfg: ModelConfig, S: int, offset=0):
    if cfg.rope_kind == "mrope" and "mrope_positions" in batch:
        return batch["mrope_positions"]
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    return pos


def run_stack_train(params, x, batch, cfg: ModelConfig, engine: ActivationEngine,
                    remat: str = "block"):
    S = x.shape[1]
    io_template = dict(
        positions=_positions_for(batch, cfg, S),
        q_pos=jnp.arange(S, dtype=jnp.int32),
        k_pos=jnp.arange(S, dtype=jnp.int32),
    )

    def body_for(eng):
        def block_fn(x, layer_params):
            io = BlockIO(mode="train", **io_template)
            return apply_block(layer_params, x, io, cfg, eng)

        if remat == "block":
            block_fn = jax.checkpoint(block_fn, prevent_cse=False)
        elif remat == "dots":
            block_fn = jax.checkpoint(
                block_fn, prevent_cse=False,
                policy=jax.checkpoint_policies.checkpoint_dots)

        def scan_body(carry, layer_params):
            x, aux = carry
            x, _, aux_i = block_fn(x, layer_params)
            return (x, aux + aux_i), None

        return scan_body

    (x, aux), _ = _scan_layers(engine, body_for, (x, jnp.float32(0.0)),
                               params["blocks"])
    return x, aux / cfg.n_layers


def run_stack_prefill(params, x, batch, cfg: ModelConfig, engine, capacity: int,
                      lengths=None):
    """Returns (x, stacked cache). Cache k/v laid out ring-style when a
    sliding window bounds capacity.

    With `lengths` (int32 [B]) the prefill is *ragged*: each row's prompt
    occupies positions [0, lengths[b]) of the (right-padded) token block;
    the returned cache is per-slot (`cur` [B], `k_pos` [B, W]) and pad
    positions are excluded from it (k_pos = -1). Causality means pad
    tokens never contaminate real rows' k/v — only trailing SSM/conv
    states, so ragged prefill of stateful archs requires lengths == S."""
    B, S = x.shape[0], x.shape[1]
    io_template = dict(
        positions=_positions_for(batch, cfg, S),
        q_pos=jnp.arange(S, dtype=jnp.int32),
        k_pos=jnp.arange(S, dtype=jnp.int32),
    )

    def body_for(eng):
        def scan_body(x, layer_params):
            io = BlockIO(mode="prefill", **io_template)
            x, cache, _ = apply_block(layer_params, x, io, cfg, eng)
            out_cache = {}
            for name, val in cache.items():
                if name in ("k", "v"):
                    out_cache[name] = (
                        _prefill_kv_to_cache(val, capacity, S)
                        if lengths is None
                        else _prefill_kv_to_cache_ragged(val, capacity,
                                                         lengths))
                else:
                    out_cache[name] = val
            return x, out_cache

        return scan_body

    x, caches = _scan_layers(engine, body_for, x, params["blocks"])
    if lengths is None:
        cache = {"layers": caches, "cur": jnp.int32(S)}
        if cfg.has_attention or cfg.parallel_mamba:
            cache["k_pos"] = _prefill_slot_positions(capacity, S)
    else:
        cache = {"layers": caches, "cur": lengths.astype(jnp.int32)}
        if cfg.has_attention or cfg.parallel_mamba:
            cache["k_pos"] = _prefill_slot_positions_ragged(capacity, lengths)
    # k_pos exists exactly when there is a KV ring to mask (matching
    # cache_spec) — a pure-SSM cache carrying a vestigial k_pos would
    # break pytree-aligned shardings in the mesh-aware serve engine
    return x, cache


def _prefill_kv_to_cache(kv, capacity: int, S: int):
    """[B,S,KV,hd] -> [B,W,KV,hd] ring-ordered cache of the last W tokens."""
    W = capacity
    if S < W:
        return jnp.pad(kv, ((0, 0), (0, W - S), (0, 0), (0, 0)))
    last = kv[:, S - W:]                                   # positions S-W..S-1
    # slot for absolute position p is p % W; positions S-W..S-1 cover every
    # residue once -> permutation: slot j holds position p with p % W == j
    j = jnp.arange(W)
    i = (j - (S - W)) % W                                  # index into `last`
    return jnp.take(last, i, axis=1)


def _ragged_ring_positions(capacity: int, lengths):
    """Absolute position held by each ring slot after a ragged prefill.

    Slot j of row b holds the unique position p in
    [max(0, len_b - W), len_b) with p % W == j; `valid` marks slots that
    hold a real (non-pad, non-evicted) position. Returns (p [B,W], valid)."""
    W = capacity
    j = jnp.arange(W, dtype=jnp.int32)[None, :]
    start = jnp.maximum(0, lengths - W).astype(jnp.int32)[:, None]  # [B,1]
    p = start + ((j - start) % W)
    return p, p < lengths[:, None]


def _prefill_kv_to_cache_ragged(kv, capacity: int, lengths):
    """[B,S,KV,hd] + lengths [B] -> [B,W,KV,hd] per-row ring cache holding
    the last min(W, len_b) *real* tokens of each row (pads excluded)."""
    p, valid = _ragged_ring_positions(capacity, lengths)
    idx = jnp.minimum(p, kv.shape[1] - 1)                  # clamp for gather
    out = jnp.take_along_axis(kv, idx[:, :, None, None], axis=1)
    return jnp.where(valid[:, :, None, None], out, jnp.zeros((), out.dtype))


def _prefill_slot_positions(capacity: int, S: int):
    W = capacity
    j = jnp.arange(W, dtype=jnp.int32)
    if S < W:
        return jnp.where(j < S, j, -1)
    return (S - W) + ((j - (S - W)) % W)


def _prefill_slot_positions_ragged(capacity: int, lengths):
    p, valid = _ragged_ring_positions(capacity, lengths)
    return jnp.where(valid, p, -1)


def run_stack_prefill_prefix(params, x, batch, cfg: ModelConfig, engine,
                             prefix_kv, prefix_len: int, capacity: int,
                             page_size: int, lengths):
    """Ragged prefill of prompt *suffixes* against an already-cached,
    page-aligned shared prefix (prefix caching, attention-only archs).

    `x` embeds the suffix tokens (right-padded to S); `prefix_kv` is the
    per-layer prefix k/v gathered from the page pool ({"k"/"v"}:
    [L, prefix_len, KV, hd], shared by every row). Each layer attends
    suffix queries over [prefix ++ suffix] keys — causal masking makes
    the row's pad keys invisible exactly as in the cold ragged path — and
    returns the suffix k/v padded to whole pages, in sequence order
    (suffix page j holds positions prefix_len + [j*ps, (j+1)*ps)).
    Requires no sliding window, so ring order == sequence order and the
    returned `cur`/`k_pos` cover positions [0, prefix_len + len_b)."""
    B, S = x.shape[0], x.shape[1]
    io_template = dict(
        positions=_positions_for(batch, cfg, S, offset=prefix_len),
        q_pos=prefix_len + jnp.arange(S, dtype=jnp.int32),
        k_pos=jnp.arange(prefix_len + S, dtype=jnp.int32),
    )
    pad = (-S) % page_size

    def body_for(eng):
        def scan_body(x, inp):
            layer_params, pre = inp
            io = BlockIO(mode="prefill",
                         cache={"k_pre": pre["k"], "v_pre": pre["v"]},
                         **io_template)
            x, cache, _ = apply_block(layer_params, x, io, cfg, eng)
            out = {}
            for name in ("k", "v"):
                kv = cache[name]
                out[name] = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0))) \
                    if pad else kv
            return x, out

        return scan_body

    x, caches = _scan_layers(engine, body_for, x,
                             (params["blocks"], prefix_kv))
    total = prefix_len + lengths.astype(jnp.int32)          # [B]
    j = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    k_pos = jnp.where(j < total[:, None], j, -1)
    return x, {"layers": caches, "cur": total, "k_pos": k_pos}


def run_stack_prefill_chunk(params, x, batch, cfg: ModelConfig, engine,
                            pool_kv, tbl_row, k_pos_row, pos, clen,
                            page_size: int):
    """Resume a ragged prefill at prompt offset `pos` for ONE paged slot
    (chunked admission: serve/engine.py interleaves these dispatches
    with decode chunks under a token budget).

    `x` embeds the chunk's tokens right-padded to S (one trace per chunk
    bucket); `pos`/`clen` are traced scalars — the chunk covers absolute
    positions [pos, pos + clen). `pool_kv` is the per-layer shared page
    pool ({"k"/"v"}: [L, P, ps, KV, hd]), `tbl_row` [n] the slot's page
    table and `k_pos_row` [n*ps] its current ring validity row (caller
    resets it on the first chunk; a prefix-cache hit starts with the
    shared pages' positions already marked).

    Attention over "my own earlier chunks" reuses the prefix-concat path
    in layers.py::_attn_branch: each layer gathers the slot's FULL
    padded ring through its page table as k_pre/v_pre and lets the
    flash mask (causal + optional sliding window + k_pos >= 0) decide
    visibility — so no page-alignment is imposed on the chunk size, and
    sliding-window rings work unchanged: a ring entry being overwritten
    by this chunk (position p - W) is masked for every query that could
    see the gathered stale value, while entries still inside some
    query's window are gathered before the chunk's scatter touches them.
    Chunk k/v then scatter into the pool page-by-token, pad lanes
    redirected to the trash page; the returned validity row marks the
    chunk's real positions (pads dropped via an out-of-bounds scatter).

    Returns (x, new pool {"k","v"} stacked [L, ...], new k_pos row)."""
    S = x.shape[1]
    ps = page_size
    W = tbl_row.shape[0] * ps                       # padded ring width
    i = jnp.arange(S, dtype=jnp.int32)
    own_pos = pos + i
    io_template = dict(
        positions=_positions_for(batch, cfg, S, offset=pos),
        q_pos=own_pos,
        k_pos=jnp.concatenate([k_pos_row,
                               jnp.where(i < clen, own_pos, -1)]),
    )
    ring_slot = own_pos % W
    w_page = jnp.where(i < clen, tbl_row[ring_slot // ps], 0)  # pads -> trash
    w_off = ring_slot % ps

    def body_for(eng):
        def scan_body(x, inp):
            layer_params, pool_k, pool_v = inp
            ring = lambda pool: pool[tbl_row].reshape((W,) + pool.shape[2:])
            io = BlockIO(mode="prefill",
                         cache={"k_pre": ring(pool_k), "v_pre": ring(pool_v)},
                         **io_template)
            x, cache, _ = apply_block(layer_params, x, io, cfg, eng)
            new_k = pool_k.at[w_page, w_off].set(
                cache["k"][0].astype(pool_k.dtype))
            new_v = pool_v.at[w_page, w_off].set(
                cache["v"][0].astype(pool_v.dtype))
            return x, (new_k, new_v)

        return scan_body

    x, (ks, vs) = _scan_layers(
        engine, body_for, x, (params["blocks"], pool_kv["k"], pool_kv["v"]))
    idx = jnp.where(i < clen, ring_slot, W)         # pads: OOB -> dropped
    new_row = k_pos_row.at[idx].set(own_pos, mode="drop")
    return x, {"k": ks, "v": vs}, new_row


def run_stack_decode(params, x, batch, cfg: ModelConfig, engine, cache):
    """One-token step. x: [B,1,d]. Returns (x, new_cache).

    Cache contract: `cur` is either a scalar (lockstep batch — every row
    at the same position) or int32 [B] (per-slot — continuous batching,
    each row independent); `k_pos` correspondingly [W] or [B, W]. The
    returned cache preserves the structure it was given, so jit-donated
    serving loops stay shape-stable.

    Paged contract: when the cache carries a `page_tbl` ([B, n] physical
    page ids per logical page), `layers.k/v` are a shared page pool
    [L, n_pages, page_size, KV, hd] instead of per-slot rows. The ring
    semantics are unchanged — logical ring slot `cur % W` lives at
    physical page `page_tbl[b, slot // page_size]`, offset
    `slot % page_size` — all with traced indices (no host sync). The
    whole pool rides the layer scan's carry, and `xs` carries each
    layer's params with its GLOBAL layer index (plus the per-slot
    `conv`/`ssm` state of hybrid archs, still sliced per layer): each
    layer scatters its B new tokens in place at [l, page, off] and
    gathers the rows' rings from `pool[l, page_tbl]`, so under donation
    the pool is updated in place and never sliced out or stacked back.
    The index stays global across the segments of a `LayerEngines`
    assignment, whose scans hand the pool on through the carry.
    Physical page 0 is the trash page: dead/unallocated logical pages
    map there, their writes are discarded by construction and their
    keys are masked (k_pos == -1)."""
    B = x.shape[0]
    cur = cache["cur"]
    per_slot = jnp.ndim(cur) > 0
    cur_b = cur if per_slot else jnp.broadcast_to(cur, (B,))       # [B]
    k_pos_vec = cache.get("k_pos")
    W = k_pos_vec.shape[-1] if k_pos_vec is not None else 0
    slot = (cur_b % W).astype(jnp.int32) if W else jnp.zeros((B,), jnp.int32)
    tbl = cache.get("page_tbl")
    # write-mask (paged serving only): rows with write_mask[b] == False
    # keep their cache bit-identical — k/v writes land on the trash
    # page, the k_pos row is untouched and cur does not advance. The
    # chunked-prefill engine decodes while some slots are still
    # mid-prefill; without the gate every decode step would scribble
    # ring slots the prefill chunks have yet to fill.
    wm = batch.get("write_mask") if tbl is not None else None
    if tbl is not None:
        ps = cache["layers"]["k"].shape[2]                 # [L,P,ps,KV,hd]
        page = jnp.take_along_axis(tbl, (slot // ps)[:, None], axis=1)[:, 0]
        if wm is not None:
            page = jnp.where(wm, page, 0)
        off = slot % ps

    if cfg.rope_kind == "mrope" and "mrope_positions" in batch:
        positions = batch["mrope_positions"]
    else:
        positions = cur_b[:, None].astype(jnp.int32)               # [B, 1]
        if cfg.rope_kind == "mrope":
            # text-only decode: all three rope sections advance together,
            # per slot (B > 1 rows may sit at different positions)
            positions = jnp.broadcast_to(positions[..., None], (B, 1, 3))

    if k_pos_vec is not None:
        kp = k_pos_vec if k_pos_vec.ndim == 2 \
            else jnp.broadcast_to(k_pos_vec[None, :], (B, W))
        upd = jnp.arange(W)[None, :] == slot[:, None]
        if wm is not None:
            upd = upd & wm[:, None]
        k_pos_new = jnp.where(upd, cur_b[:, None], kp)             # [B, W]
    else:
        k_pos_new = None

    # the paged pool rides the carry; per-slot state is sliced per layer
    layers = cache["layers"]
    pool = {n: layers[n] for n in ("k", "v")} if tbl is not None else {}
    per_layer = {n: a for n, a in layers.items() if n not in pool}

    def body_for(eng):
        def scan_body(carry, inp):
            (x, pool), (layer_params, l, layer_cache) = carry, inp
            lcache = dict(layer_cache, **pool)
            if tbl is not None:
                lcache.update(layer=l, page=page, off=off, page_tbl=tbl)
            else:
                lcache["slot"] = slot
            io = BlockIO(mode="decode", positions=positions, q_pos=cur_b,
                         k_pos=k_pos_new, cache=lcache)
            x, new_cache, _ = apply_block(layer_params, x, io, cfg, eng)
            # preserve untouched entries (e.g. nothing for pure attn)
            merged = {k: new_cache.get(k, v) for k, v in layer_cache.items()}
            return (x, {n: new_cache[n] for n in pool}), merged

        return scan_body

    (x, pool), per_layer = _scan_layers(
        engine, body_for, (x, pool),
        (params["blocks"], jnp.arange(cfg.n_layers), per_layer))
    new_layer_caches = dict(per_layer, **pool)
    adv = 1 if wm is None else wm.astype(jnp.int32)
    new_cache = {"layers": new_layer_caches, "cur": cur + adv}
    if k_pos_new is not None:
        new_cache["k_pos"] = k_pos_new if (per_slot or k_pos_vec.ndim == 2) \
            else k_pos_new[0]
    if tbl is not None:
        new_cache["page_tbl"] = tbl
    return x, new_cache


# ---------------------------------------------------------------------------
# cache construction (shapes for dry-run / serving)
# ---------------------------------------------------------------------------

def cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int, dtype=None,
               per_slot: bool = False):
    """ShapeDtypeStruct tree describing the cache at a given fill level.
    `per_slot=True` gives the continuous-batching layout: every row has
    its own position (`cur` [B], `k_pos` [B, W])."""
    cdt = dtype or jnp.dtype(cfg.compute_dtype)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    W = cache_capacity(cfg, seq_len)
    layers: dict[str, Any] = {}
    sds = jax.ShapeDtypeStruct
    if cfg.has_attention or cfg.parallel_mamba:
        layers["k"] = sds((L, batch, W, KV, hd), cdt)
        layers["v"] = sds((L, batch, W, KV, hd), cdt)
    if cfg.use_mamba or cfg.parallel_mamba:
        layers["conv"] = sds((L, batch, cfg.conv_kernel - 1, cfg.d_inner_), cdt)
        layers["ssm"] = sds((L, batch, cfg.d_inner_, cfg.ssm_state), jnp.float32)
    spec = {"layers": layers,
            "cur": sds((batch,) if per_slot else (), jnp.int32)}
    if cfg.has_attention or cfg.parallel_mamba:
        spec["k_pos"] = sds((batch, W) if per_slot else (W,), jnp.int32)
    return spec


def cache_axes(cfg: ModelConfig, per_slot: bool = False):
    """Logical axes tree matching cache_spec (for shardings)."""
    layers: dict[str, Any] = {}
    if cfg.has_attention or cfg.parallel_mamba:
        layers["k"] = ("layer", "batch", "seq", "act_kv", None)
        layers["v"] = ("layer", "batch", "seq", "act_kv", None)
    if cfg.use_mamba or cfg.parallel_mamba:
        layers["conv"] = ("layer", "batch", None, "act_dinner")
        layers["ssm"] = ("layer", "batch", "act_dinner", None)
    axes = {"layers": layers, "cur": ("batch",) if per_slot else ()}
    if cfg.has_attention or cfg.parallel_mamba:
        axes["k_pos"] = ("batch", None) if per_slot else (None,)
    return axes


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               per_slot: bool = False):
    """Zero-filled cache (serving from scratch). Per-slot caches start
    fully invalid: cur = 0, every k_pos = -1 (masked)."""
    spec = cache_spec(cfg, batch, seq_len, per_slot=per_slot)

    def zero(s):
        z = jnp.zeros(s.shape, s.dtype)
        return z

    cache = jax.tree.map(zero, spec)
    cache["cur"] = jnp.zeros((batch,), jnp.int32) if per_slot else jnp.int32(0)
    if "k_pos" in cache:
        cache["k_pos"] = jnp.full(spec["k_pos"].shape, -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# paged cache (page-pool contract; serve/engine.py cache="paged")
# ---------------------------------------------------------------------------

def pages_per_slot(cfg: ModelConfig, seq_len: int, page_size: int) -> int:
    """Logical pages per decode slot: the ring capacity rounded up to
    whole pages. The paged ring width is pages_per_slot * page_size —
    padding the ring is semantically free because attention validity is
    mask-driven (k_pos), not width-driven."""
    return -(-cache_capacity(cfg, seq_len) // page_size)


def paged_cache_spec(cfg: ModelConfig, slots: int, n_pages: int,
                     page_size: int, seq_len: int, dtype=None):
    """ShapeDtypeStruct tree for the paged serve cache: one shared k/v
    page pool [L, n_pages, page_size, KV, hd] per layer plus per-slot
    page tables [slots, pages_per_slot] mapping logical ring pages to
    pool pages. SSM/conv states (hybrid archs) stay per-slot — they are
    O(1) per row, paging them buys nothing."""
    if not (cfg.has_attention or cfg.parallel_mamba):
        raise ValueError(f"{cfg.name}: paged cache requires a KV ring "
                         "(pure-SSM stacks have nothing to page)")
    cdt = dtype or jnp.dtype(cfg.compute_dtype)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    n_slot = pages_per_slot(cfg, seq_len, page_size)
    sds = jax.ShapeDtypeStruct
    layers: dict[str, Any] = {
        "k": sds((L, n_pages, page_size, KV, hd), cdt),
        "v": sds((L, n_pages, page_size, KV, hd), cdt),
    }
    if cfg.use_mamba or cfg.parallel_mamba:
        layers["conv"] = sds((L, slots, cfg.conv_kernel - 1, cfg.d_inner_), cdt)
        layers["ssm"] = sds((L, slots, cfg.d_inner_, cfg.ssm_state), jnp.float32)
    return {"layers": layers,
            "cur": sds((slots,), jnp.int32),
            "k_pos": sds((slots, n_slot * page_size), jnp.int32),
            "page_tbl": sds((slots, n_slot), jnp.int32)}


def paged_cache_axes(cfg: ModelConfig):
    """Logical axes tree matching paged_cache_spec. The pool dim is
    "pages" (host-addressed like decode slots — see serve_rules), the
    in-page dim is plain sequence; heads shard exactly as per-slot k/v."""
    layers: dict[str, Any] = {
        "k": ("layer", "pages", "seq", "act_kv", None),
        "v": ("layer", "pages", "seq", "act_kv", None),
    }
    if cfg.use_mamba or cfg.parallel_mamba:
        layers["conv"] = ("layer", "batch", None, "act_dinner")
        layers["ssm"] = ("layer", "batch", "act_dinner", None)
    return {"layers": layers, "cur": ("batch",), "k_pos": ("batch", None),
            "page_tbl": ("batch", None)}


def init_paged_cache(cfg: ModelConfig, slots: int, n_pages: int,
                     page_size: int, seq_len: int):
    """Zero page pool; every page table entry points at the trash page
    (physical page 0) and every k_pos is -1 (masked)."""
    spec = paged_cache_spec(cfg, slots, n_pages, page_size, seq_len)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    cache["k_pos"] = jnp.full(spec["k_pos"].shape, -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# step functions (lowered by the launcher)
# ---------------------------------------------------------------------------

def loss_fn(params, batch, cfg: ModelConfig, engine: ActivationEngine,
            remat: str = "block", z_loss: float = 1e-4):
    tokens, labels = batch["tokens"], batch["labels"]
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, aux = run_stack_train(params, x, batch, cfg, engine, remat)
    x = apply_norm(params["ln_f"], x, cfg)
    logits = lm_logits(params, x, cfg)                     # f32
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    nll = (lse - ll).mean()
    total = nll + aux + z_loss * (lse ** 2).mean()
    return total, {"nll": nll, "aux": aux}


def forward_fn(params, batch, cfg: ModelConfig, engine: ActivationEngine):
    """Full-sequence logits, no cache (tests / evaluation)."""
    tokens = batch["tokens"]
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, _ = run_stack_train(params, x, batch, cfg, engine, remat="none")
    x = apply_norm(params["ln_f"], x, cfg)
    return lm_logits(params, x, cfg)


def prefill_fn(params, batch, cfg: ModelConfig, engine: ActivationEngine,
               capacity: int | None = None, lengths=None):
    """With `lengths` (int32 [B], or a batch["lengths"] entry) the prompt
    block is treated as ragged/right-padded: the returned logits are read
    at each row's last *real* token and the cache is per-slot."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    capacity = capacity or cache_capacity(cfg, S)
    if lengths is None:
        lengths = batch.get("lengths")
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, cache = run_stack_prefill(params, x, batch, cfg, engine, capacity,
                                 lengths=lengths)
    x = apply_norm(params["ln_f"], x, cfg)
    if lengths is None:
        last = x[:, -1:]
    else:
        idx = (lengths - 1).astype(jnp.int32)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)         # [B, 1, d]
    logits = lm_logits(params, last, cfg)[:, 0]
    return logits, cache


def prefill_prefix_fn(params, batch, cfg: ModelConfig,
                      engine: ActivationEngine, prefix_kv, prefix_len: int,
                      capacity: int, page_size: int):
    """Prefix-cached admission step: ragged prefill of prompt suffixes
    over a shared page-aligned prefix (run_stack_prefill_prefix). Logits
    are read at each row's last real *suffix* token; the returned cache
    covers only the suffix (page-shaped k/v) — prefix pages are already
    in the pool and are never rewritten."""
    tokens = batch["tokens"]
    lengths = batch["lengths"]
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, cache = run_stack_prefill_prefix(params, x, batch, cfg, engine,
                                        prefix_kv, prefix_len, capacity,
                                        page_size, lengths)
    x = apply_norm(params["ln_f"], x, cfg)
    idx = (lengths - 1).astype(jnp.int32)[:, None, None]
    last = jnp.take_along_axis(x, idx, axis=1)             # [B, 1, d]
    logits = lm_logits(params, last, cfg)[:, 0]
    return logits, cache


def prefill_chunk_fn(params, batch, cfg: ModelConfig,
                     engine: ActivationEngine, pool_kv, tbl_row, k_pos_row,
                     pos, clen, page_size: int):
    """Chunked-admission step: one chunk of one slot's prompt resumed at
    offset `pos` (run_stack_prefill_chunk). Logits are read at the
    chunk's last real token — only meaningful on the final chunk, where
    the engine samples the first generated token from them."""
    tokens = batch["tokens"]                               # [1, S]
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, new_kv, new_row = run_stack_prefill_chunk(
        params, x, batch, cfg, engine, pool_kv, tbl_row, k_pos_row,
        pos, clen, page_size)
    x = apply_norm(params["ln_f"], x, cfg)
    idx = jnp.reshape(clen - 1, (1, 1, 1)).astype(jnp.int32)
    last = jnp.take_along_axis(x, idx, axis=1)             # [1, 1, d]
    logits = lm_logits(params, last, cfg)[:, 0]            # [1, V]
    return logits, new_kv, new_row


def decode_fn(params, batch, cache, cfg: ModelConfig, engine: ActivationEngine):
    tokens = batch["tokens"]                               # [B, 1(,K)]
    engine = _bind_engine(engine, params)
    x = embed_tokens(params, tokens, cfg, batch.get("patch_embeds"))
    x, cache = run_stack_decode(params, x, batch, cfg, engine, cache)
    x = apply_norm(params["ln_f"], x, cfg)
    logits = lm_logits(params, x, cfg)[:, 0]
    return logits, cache
