"""Model building blocks (pure JAX, functional params-in/activations-out).

Every nonlinearity routes through the configured ActivationEngine — the
paper's CR-spline unit is a config flip away on every architecture.

Initializers return trees of Boxed(value, logical_axes); the stack-level
init unboxes them into (params, axes) trees. All attention runs through a
flash-style doubly-chunked accumulator (lax.scan over KV chunks inside a
scan over Q chunks) so 32k-token prefill lowers with bounded temps.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.activations import ActivationEngine
from repro.parallel.partition import Boxed, box, logical_constraint as lc

from .config import ModelConfig

NEG_INF = -1.0e30


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(key, cfg: ModelConfig, d: int | None = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": box(("embed",), jnp.ones((d,), jnp.float32))}
    return {}  # layernorm_np: non-parametric (olmo)


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    else:  # non-parametric layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """Per-head RMSNorm over head_dim (qwen3 qk-norm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig):
    hd = cfg.head_dim_
    return 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))


def apply_rope(x, positions, cfg: ModelConfig):
    """x: [..., S, H, hd]; positions: [B_or_1, S] (standard) or
    [B_or_1, S, 3] (M-RoPE, qwen2-vl). Rotation in f32."""
    if cfg.rope_kind == "none":
        return x
    hd = cfg.head_dim_
    inv = jnp.asarray(rope_freqs(cfg), jnp.float32)          # [hd/2]
    if cfg.rope_kind == "mrope":
        # positions [..., S, 3] -> per-frequency-section (t/h/w) choice
        secs = cfg.mrope_sections
        sec_id = np.concatenate([np.full((s,), i) for i, s in enumerate(secs)])
        sec_id = jnp.asarray(sec_id, jnp.int32)              # [hd/2]
        p3 = positions.astype(jnp.float32)                   # [B, S, 3]
        pos = jnp.einsum("bsk,fk->bsf", p3,
                         jax.nn.one_hot(sec_id, 3, dtype=jnp.float32))  # [B,S,hd/2]
        angles = pos * inv[None, None, :]
    else:
        pos = positions.astype(jnp.float32)                  # [B, S]
        angles = pos[..., None] * inv                        # [B, S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                      # [B, S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, flash-style chunked, SWA, qk-norm, bias, softcap)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 5)
    p = {
        "wq": box(("embed", "heads", "head_dim"), _init(ks[0], (d, h, hd))),
        "wk": box(("embed", "kv", "head_dim"), _init(ks[1], (d, kvh, hd))),
        "wv": box(("embed", "kv", "head_dim"), _init(ks[2], (d, kvh, hd))),
        "wo": box(("heads", "head_dim", "embed"),
                  _init(ks[3], (h, hd, d), scale=1.0 / math.sqrt(h * hd))),
    }
    if cfg.qkv_bias:
        p["bq"] = box(("heads", "head_dim"), jnp.zeros((h, hd), jnp.float32))
        p["bk"] = box(("kv", "head_dim"), jnp.zeros((kvh, hd), jnp.float32))
        p["bv"] = box(("kv", "head_dim"), jnp.zeros((kvh, hd), jnp.float32))
    if cfg.qk_norm:
        p["q_norm"] = box(("head_dim",), jnp.ones((hd,), jnp.float32))
        p["k_norm"] = box(("head_dim",), jnp.ones((hd,), jnp.float32))
    return p


def _qkv(params, x, positions, cfg: ModelConfig):
    cdt = dtype_of(cfg)
    q = jnp.einsum("bsd,dhx->bshx", x, params["wq"].astype(cdt))
    k = jnp.einsum("bsd,dkx->bskx", x, params["wk"].astype(cdt))
    v = jnp.einsum("bsd,dkx->bskx", x, params["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_head_norm(params["q_norm"], q)
        k = rms_head_norm(params["k_norm"], k)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    q = lc(q, "batch", "seq", "act_heads", None)
    k = lc(k, "batch", "seq", "act_kv", None)
    v = lc(v, "batch", "seq", "act_kv", None)
    return q, k, v


def _flash_chunk_scan(q, k, v, q_pos, k_pos, cfg: ModelConfig, engine):
    """Online-softmax attention for one Q chunk over all KV chunks.

    q: [B, qc, H, hd]; k/v: [B, S, H, hd] (GQA heads pre-expanded by the
    caller); positions int32. Returns [B, qc, H, hd].

    Sharding note (§Perf iteration 1): every intermediate keeps the flat
    head dim H, which the rule table maps to the 'model' mesh axis. An
    earlier version factored H into (KV, G) — PartitionSpec cannot split
    one mesh axis across two tensor dims, so GSPMD replicated the score
    tensors across 'model' in the scan backward and inserted per-chunk
    all-gathers + full-remat copies (measured: 29.3s collective /
    20.3s memory per step on qwen3-0.6b train_4k, 256 chips). Explicit
    logical constraints on the scores and the scan carry keep the layout
    stable across loop iterations.
    """
    B, qc, H, hd = q.shape
    S = k.shape[1]
    kc = min(cfg.kv_chunk, S)
    n_kv = S // kc
    assert S % kc == 0, (S, kc)  # caller pads
    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale

    k_r = k.reshape(B, n_kv, kc, H, hd)
    v_r = v.reshape(B, n_kv, kc, H, hd)
    kp_r = k_pos.reshape(n_kv, kc)

    def step(carry, inputs):
        acc, m, l = carry
        kc_blk, vc_blk, kp_blk = inputs                     # [B,kc,H,hd], [kc]
        s = jnp.einsum("bqhx,bkhx->bhqk", qf.astype(jnp.float32),
                       kc_blk.astype(jnp.float32))
        s = lc(s, "batch", "act_heads", None, None)
        mask = kp_blk[None, :] <= q_pos[:, None]            # causal [qc, kc]
        if cfg.sliding_window is not None:
            mask &= kp_blk[None, :] > q_pos[:, None] - cfg.sliding_window
        mask &= (kp_blk >= 0)[None, :]                      # ring-buffer validity
        if cfg.logit_softcap:
            s = cfg.logit_softcap * engine.tanh(s / cfg.logit_softcap)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhx->bhqx", p, vc_blk.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        acc_new = lc(acc_new, "batch", "act_heads", None, None)
        return (acc_new, m_new, l_new), None

    acc0 = lc(jnp.zeros((B, H, qc, hd), jnp.float32),
              "batch", "act_heads", None, None)
    m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, qc), jnp.float32)
    # §Perf iteration 2: remat the chunk step. Without this, reverse-mode
    # AD of the scan stacks the [B,H,qc,kc] probability tensor for every
    # KV chunk ([n_kv,B,H,qc,kc] residuals — measured 1.5e12 bytes/step on
    # qwen3 train_4k). Flash attention's defining trick is recomputing
    # scores in the backward pass; jax.checkpoint does exactly that here.
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False), (acc0, m0, l0),
        (jnp.moveaxis(k_r, 1, 0), jnp.moveaxis(v_r, 1, 0), kp_r))
    out = acc / jnp.maximum(l, 1e-20)[..., None]            # [B,H,qc,hd]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)          # [B,qc,H,hd]


def expand_kv_heads(kv, G: int):
    """GQA -> flat heads: [B, S, KV, hd] -> [B, S, KV*G, hd], head h
    served by kv-head h // G. A G-fold repeat is cheap (recomputed under
    remat) and buys clean 'model'-axis sharding of every attention
    intermediate; its transpose (segment-sum over G) is equally clean."""
    if G == 1:
        return kv
    return lc(jnp.repeat(kv, G, axis=2), "batch", "seq", "act_heads", None)


def flash_attention(q, k, v, q_pos, k_pos, cfg: ModelConfig, engine):
    """Doubly-chunked causal attention.
    q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] (expanded to H internally).
    q_pos: [Sq] absolute positions; k_pos: [Skv] (-1 = invalid slot)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    # pad to chunk multiples (pad keys get position -1 => masked out;
    # pad query rows are sliced off after)
    qc = min(cfg.q_chunk, Sq)
    kc = min(cfg.kv_chunk, Skv)
    pq = (-Sq) % qc
    pk = (-Skv) % kc
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
    out = _flash_padded(q, k, v, q_pos, k_pos, cfg, engine, qc)
    return out[:, :Sq] if pq else out


def _flash_padded(q, k, v, q_pos, k_pos, cfg: ModelConfig, engine, qc: int):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    k = expand_kv_heads(k, G)
    v = expand_kv_heads(v, G)
    n_q = Sq // qc

    if n_q == 1:
        out = _flash_chunk_scan(q, k, v, q_pos, k_pos, cfg, engine)
    else:
        qs = jnp.moveaxis(q.reshape(B, n_q, qc, H, hd), 1, 0)
        qp = q_pos.reshape(n_q, qc)

        def per_chunk(carry, inputs):
            qi, qpi = inputs
            return carry, _flash_chunk_scan(qi, k, v, qpi, k_pos, cfg, engine)

        _, outs = jax.lax.scan(per_chunk, (), (qs, qp))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd)
    return out


def decode_attention(q, k_cache, v_cache, q_pos, k_pos, cfg: ModelConfig, engine):
    """Single-token attention over the cache. q: [B, 1, H, hd];
    k/v_cache: [B, W, KV, hd]; q_pos: [B] per-slot query positions;
    k_pos: [B, W] per-slot absolute key positions (-1 empty). A lockstep
    batch is the special case where every row agrees."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache.astype(jnp.float32))
    mask = (k_pos <= q_pos[:, None]) & (k_pos >= 0)         # [B, W]
    if cfg.sliding_window is not None:
        mask &= k_pos > q_pos[:, None] - cfg.sliding_window
    if cfg.logit_softcap:
        s = cfg.logit_softcap * engine.tanh(s / cfg.logit_softcap)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention_out(params, ctx, cfg: ModelConfig):
    cdt = dtype_of(cfg)
    return jnp.einsum("bshx,hxd->bsd", ctx, params["wo"].astype(cdt))


# ---------------------------------------------------------------------------
# MLP / GLU (dense + per-expert weights reused by MoE)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": box(("embed", "mlp"), _init(ks[1], (d, f))),
        "w_down": box(("mlp", "embed"), _init(ks[2], (f, d))),
    }
    if cfg.glu:
        p["w_gate"] = box(("embed", "mlp"), _init(ks[0], (d, f)))
    return p


def mlp_fusable(cfg: ModelConfig, engine: ActivationEngine) -> bool:
    """fuse_mlp preconditions: a gated FFN whose activation exists as an
    epilogue, under an approximant-scheme engine (the fused kernel IS
    that scheme's datapath — fusing under a non-approximant backend
    would silently change numerics). Checked here and at step-build
    time (launch/steps.py)."""
    from repro.kernels.epilogue import EPILOGUES  # lazy: avoid cycle
    return (cfg.fuse_mlp and cfg.glu and cfg.mlp_act in EPILOGUES
            and engine.act_impl is not None)


def apply_mlp(params, x, cfg: ModelConfig, engine: ActivationEngine):
    cdt = dtype_of(cfg)
    if mlp_fusable(cfg, engine):
        # one kernel: gate/up matmuls + approximant epilogue on the f32
        # accumulator — the gate projection never round-trips to HBM.
        from repro.kernels import epilogue as epi, ops as kernel_ops
        ecfg = engine.cfg
        # a bound engine's trainable tanh params ride into the kernel;
        # the softplus epilogue reads its own residual table instead
        bound = None if cfg.mlp_act == "softplus" else engine.act_params
        if engine.act_impl == "cr_spline":
            table = epi.table_for(cfg.mlp_act, ecfg.x_max, ecfg.depth)
            h = kernel_ops.fused_glu(x, params["w_gate"].astype(cdt),
                                     params["w_up"].astype(cdt), table,
                                     act=cfg.mlp_act, params=bound)
        else:
            h = kernel_ops.fused_glu(x, params["w_gate"].astype(cdt),
                                     params["w_up"].astype(cdt),
                                     act=cfg.mlp_act, method=engine.act_impl,
                                     depth=ecfg.depth, x_max=ecfg.x_max,
                                     degree=ecfg.degree, params=bound)
    else:
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(cdt))
        if cfg.glu:
            gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(cdt))
            h = engine(cfg.mlp_act, gate) * up
        else:
            h = engine(cfg.mlp_act, up)
    h = lc(h, "batch", "seq", "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(cdt))


# ---------------------------------------------------------------------------
# MoE: token-choice top-k, sort-based dispatch + ragged_dot (dropless)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": box(("embed", "expert"), _init(ks[0], (d, e))),
        "w_gate": box(("expert", "embed", "mlp"),
                      _init(ks[1], (e, d, f), scale=1.0 / math.sqrt(d))),
        "w_up": box(("expert", "embed", "mlp"),
                    _init(ks[2], (e, d, f), scale=1.0 / math.sqrt(d))),
        "w_down": box(("expert", "mlp", "embed"),
                      _init(ks[3], (e, f, d), scale=1.0 / math.sqrt(f))),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg)
    return p


def apply_moe(params, x, cfg: ModelConfig, engine: ActivationEngine):
    if cfg.moe_impl == "gshard":
        return apply_moe_gshard(params, x, cfg, engine)
    return apply_moe_ragged(params, x, cfg, engine)


def apply_moe_gshard(params, x, cfg: ModelConfig, engine: ActivationEngine):
    """GShard/Switch-style capacity-bounded MoE with grouped one-hot
    einsum dispatch (§Perf llama4 hillclimb).

    Why: the dropless sort-based dispatch (apply_moe_ragged) routes with
    argsort + data-dependent gather/scatter over the token dim — GSPMD
    cannot shard a data-dependent permutation, so it replicated the
    [T, d] dispatch tensors and all-reduced them per layer (measured
    1.25e13 collective bytes/step on llama4-scout train_4k = 93% of all
    collective traffic). Here dispatch/combine are einsums against
    one-hot masks built from per-(batch row, expert) running positions:
    everything shards over the batch dim and the expert-dim contraction
    lowers to the canonical EP exchange. Tokens beyond an expert's
    capacity C = ceil(S * capacity_factor / E) per slot are dropped
    (combine weight 0) — the standard GShard trade; the aux loss keeps
    the router balanced so drops stay rare.

    x: [B, S, d]; batch rows double as dispatch groups.
    """
    cdt = dtype_of(cfg)
    B0, S0, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    # fixed-size dispatch groups: capacity (and with it the one-hot
    # dispatch einsum cost per token, E*C*d) must not grow with sequence
    # length — at 32k tokens/row an S-proportional capacity made dispatch
    # flops rival 32k attention (measured: mixtral prefill_32k went
    # compute-bound at 40.5 s/device). Rows are split into group_size
    # segments; routing is per-token so regrouping is semantics-free
    # (only the capacity-drop boundaries move).
    g = min(cfg.moe_group_size, S0)
    if S0 % g:
        g = S0  # fallback: ragged tail would change semantics
    x = x.reshape(B0 * (S0 // g), g, d)
    B, S, _ = x.shape
    cap = int(math.ceil(S * cfg.capacity_factor / e))

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                   # [B, S, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)   # renormalize

    # aux load-balancing loss (GShard form, over all tokens)
    me = jnp.mean(probs, axis=(0, 1))
    ce_frac = jnp.mean(
        jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(2), axis=(0, 1))
    aux = e * jnp.sum(me * ce_frac)

    y = jnp.zeros((B, S, d), jnp.float32)
    # per-expert running positions shared across the k slots (slot 0 first)
    pos_base = jnp.zeros((B, e), jnp.float32)
    for slot in range(k):
        idx = top_i[..., slot]                               # [B, S]
        w = top_w[..., slot]                                 # [B, S]
        oh_e = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # [B, S, E]
        pos = jnp.cumsum(oh_e, axis=1) - 1.0 + pos_base[:, None, :]
        pos_tok = jnp.einsum("bse,bse->bs", pos, oh_e)       # [B, S]
        keep = (pos_tok < cap).astype(jnp.float32)
        oh_c = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap,
                              dtype=jnp.float32) * keep[..., None]
        pos_base = pos_base + jnp.sum(oh_e, axis=1)

        # dispatch: [B,S,E]x[B,S,C]x[B,S,d] -> [E, B, C, d]
        xe = jnp.einsum("bse,bsc,bsd->ebcd", oh_e, oh_c,
                        x.astype(jnp.float32)).astype(cdt)
        xe = lc(xe, None, "batch", None, None)
        gate = jnp.einsum("ebcd,edf->ebcf", xe, params["w_gate"].astype(cdt))
        up = jnp.einsum("ebcd,edf->ebcf", xe, params["w_up"].astype(cdt))
        h = engine(cfg.mlp_act, gate) * up if cfg.glu else engine(cfg.mlp_act, up)
        h = lc(h, None, "batch", None, "act_mlp")
        out_e = jnp.einsum("ebcf,efd->ebcd", h, params["w_down"].astype(cdt))
        # combine with routing weights (dropped tokens contribute 0)
        y = y + jnp.einsum("bse,bsc,ebcd->bsd", oh_e, oh_c * w[..., None],
                           out_e.astype(jnp.float32))

    out = y.astype(x.dtype).reshape(B0, S0, d)
    if cfg.shared_expert:
        out = out + apply_mlp(params["shared"], x.reshape(B0, S0, d),
                              cfg, engine)
    return out, cfg.router_aux_weight * aux


def apply_moe_ragged(params, x, cfg: ModelConfig, engine: ActivationEngine):
    """x: [B, S, d]. Token-choice top-k with mixtral-style renormalized
    softmax over the selected experts; dropless sort-based dispatch.
    Exact (no token dropping) but the data-dependent permutation does not
    shard under pjit — use for single-host runs and as the semantic
    reference for the gshard path."""
    cdt = dtype_of(cfg)
    B, S, d = x.shape
    T = B * S
    k = cfg.top_k
    e = cfg.n_experts
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                    # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)    # renormalize

    # aux load-balancing loss (GShard/mixtral form)
    me = jnp.mean(probs, axis=0)
    ce_frac = jnp.mean(
        (jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(1)), axis=0)
    aux = e * jnp.sum(me * ce_frac)

    # sort expanded (token, expert) pairs by expert
    flat_expert = top_i.reshape(-1)                           # [T*k]
    sort_idx = jnp.argsort(flat_expert)
    token_idx = jnp.repeat(jnp.arange(T), k)[sort_idx]
    xs = jnp.take(xt, token_idx, axis=0)                      # [T*k, d]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    gate = jax.lax.ragged_dot(xs, params["w_gate"].astype(cdt), group_sizes)
    up = jax.lax.ragged_dot(xs, params["w_up"].astype(cdt), group_sizes)
    h = engine(cfg.mlp_act, gate) * up if cfg.glu else engine(cfg.mlp_act, up)
    out_s = jax.lax.ragged_dot(h, params["w_down"].astype(cdt), group_sizes)

    w_sorted = top_w.reshape(-1)[sort_idx].astype(out_s.dtype)
    combined = jnp.zeros((T, d), out_s.dtype).at[token_idx].add(
        out_s * w_sorted[:, None])
    out = combined.reshape(B, S, d).astype(x.dtype)
    if cfg.shared_expert:
        out = out + apply_mlp(params["shared"], x, cfg, engine)
    return out, cfg.router_aux_weight * aux


# ---------------------------------------------------------------------------
# Mamba-1 (selective SSM) — falcon-mamba / hymba branch
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig):
    d, di, N, dtr, ck = (cfg.d_model, cfg.d_inner_, cfg.ssm_state,
                         cfg.dt_rank_, cfg.conv_kernel)
    ks = jax.random.split(key, 7)
    # S4D-real initialization for A
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    p = {
        "in_proj": box(("embed", "dinner"), _init(ks[0], (d, 2 * di))),
        "conv_w": box(("conv", "dinner"), _init(ks[1], (ck, di), scale=1.0 / math.sqrt(ck))),
        "conv_b": box(("dinner",), jnp.zeros((di,), jnp.float32)),
        "x_proj": box(("dinner", "dt"), _init(ks[2], (di, dtr + 2 * N))),
        "dt_proj_w": box(("dt", "dinner"), _init(ks[3], (dtr, di))),
        "dt_proj_b": box(("dinner",),
                         jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                             ks[4], (di,), minval=math.log(1e-3),
                             maxval=math.log(1e-1))))) ),
        "A_log": box(("dinner", "state"), jnp.log(A)),
        "D": box(("dinner",), jnp.ones((di,), jnp.float32)),
        "out_proj": box(("dinner", "embed"), _init(ks[5], (di, d), scale=1.0 / math.sqrt(di))),
    }
    return p


def _mamba_inner(params, xz, conv_state, ssm_state, cfg: ModelConfig,
                 engine: ActivationEngine):
    """Shared mamba core over a sequence chunk.
    xz: [B, S, 2*di]; conv_state: [B, ck-1, di]; ssm_state: [B, di, N].
    Returns (y [B,S,d_inner->projected later], new_conv_state, new_ssm_state)."""
    di, N, dtr, ck = cfg.d_inner_, cfg.ssm_state, cfg.dt_rank_, cfg.conv_kernel
    B, S, _ = xz.shape
    xin, z = xz[..., :di], xz[..., di:]

    # depthwise causal conv1d along S with carried state
    xpad = jnp.concatenate([conv_state.astype(xin.dtype), xin], axis=1)  # [B, S+ck-1, di]
    conv_w = params["conv_w"].astype(xin.dtype)                # [ck, di]
    xc = sum(xpad[:, i:i + S, :] * conv_w[i] for i in range(ck))
    xc = xc + params["conv_b"].astype(xin.dtype)
    new_conv_state = xpad[:, S:, :] if ck > 1 else conv_state
    xc = engine.silu(xc)
    xc = lc(xc, "batch", "seq", "act_dinner")

    # input-dependent SSM parameters
    proj = jnp.einsum("bsd,dk->bsk", xc, params["x_proj"].astype(xc.dtype))
    dt_in, Bc, Cc = (proj[..., :dtr], proj[..., dtr:dtr + N],
                     proj[..., dtr + N:])
    dt = jnp.einsum("bsr,rd->bsd", dt_in, params["dt_proj_w"].astype(xc.dtype))
    dt = engine.softplus(dt.astype(jnp.float32) + params["dt_proj_b"])  # [B,S,di]
    A = -jnp.exp(params["A_log"])                              # [di, N]

    # §Perf (falcon-mamba hillclimb): the discretized dA = exp(dt*A) and
    # dBx = dt*x*B live only INSIDE the (rematted) scan body — an earlier
    # version materialized both as [B,S,di,N] before the scan and AD then
    # stacked them again as residuals (~4x the state-expanded sequence in
    # HBM). Here the body recomputes them from the [B,S,di]-sized dt/x
    # and [B,S,N]-sized B rows in the backward pass; unroll=8 amortizes
    # the per-step carry buffer bounce across 8 fused timesteps.
    dtx = dt * xc.astype(jnp.float32)                          # [B,S,di]

    def step(h, inputs):
        dt_t, dtx_t, B_t, C_t = inputs        # [B,di],[B,di],[B,N],[B,N]
        dA_t = jnp.exp(dt_t[..., None] * A)                    # [B,di,N]
        h = dA_t * h + dtx_t[..., None] * B_t[:, None, :]
        y_t = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y_t

    (h_last, ys) = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False),
        ssm_state.astype(jnp.float32),
        (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(dtx, 1, 0),
         jnp.moveaxis(Bc.astype(jnp.float32), 1, 0),
         jnp.moveaxis(Cc.astype(jnp.float32), 1, 0)),
        unroll=8)
    y = jnp.moveaxis(ys, 0, 1)                                 # [B,S,di]
    y = y + xc.astype(jnp.float32) * params["D"]
    y = y * engine.silu(z.astype(jnp.float32))
    return y.astype(xz.dtype), new_conv_state, h_last


def apply_mamba(params, x, cfg: ModelConfig, engine, conv_state=None,
                ssm_state=None):
    """Full-sequence mamba block. Returns (out [B,S,d], conv_state, ssm_state)."""
    cdt = dtype_of(cfg)
    B, S, _ = x.shape
    di, ck, N = cfg.d_inner_, cfg.conv_kernel, cfg.ssm_state
    if conv_state is None:
        conv_state = jnp.zeros((B, ck - 1, di), cdt)
    if ssm_state is None:
        ssm_state = jnp.zeros((B, di, N), jnp.float32)
    xz = jnp.einsum("bsd,dk->bsk", x, params["in_proj"].astype(cdt))
    y, conv_state, ssm_state = _mamba_inner(params, xz, conv_state, ssm_state,
                                            cfg, engine)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"].astype(cdt))
    return out, conv_state, ssm_state


# ---------------------------------------------------------------------------
# transformer block (dense / moe / mamba / hymba-parallel)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"ln1": init_norm(ks[0], cfg)}
    if cfg.use_mamba:
        p["mamba"] = init_mamba(ks[1], cfg)
    elif cfg.parallel_mamba:
        p["attn"] = init_attention(ks[1], cfg)
        p["mamba"] = init_mamba(ks[2], cfg)
        p["ln_attn_out"] = init_norm(ks[3], cfg)
        p["ln_mamba_out"] = init_norm(ks[4], cfg)
    else:
        p["attn"] = init_attention(ks[1], cfg)
    if cfg.has_ffn:
        p["ln2"] = init_norm(ks[5], cfg)
        key_ffn = jax.random.fold_in(key, 99)
        p["ffn"] = init_moe(key_ffn, cfg) if cfg.n_experts > 0 else init_mlp(key_ffn, cfg)
    return p


@dataclasses.dataclass
class BlockIO:
    """What a block consumes/produces besides the hidden state."""
    positions: Any = None        # [B?, S] or [B, S, 3] (mrope)
    q_pos: Any = None            # [S] (train/prefill) or [B] (decode,
                                 # per-slot) absolute query positions
    k_pos: Any = None            # [S] (train/prefill) or [B, W] (decode,
                                 # per-slot) absolute key positions
    mode: str = "train"          # train | prefill | decode
    cache: dict | None = None    # per-layer cache slices (decode/prefill out)
    aux_loss: Any = 0.0


def _attn_branch(p, xn, io: BlockIO, cfg: ModelConfig, engine):
    new_cache = {}
    if io.mode == "decode":
        q, k_new, v_new = _qkv(p, xn, io.positions, cfg)
        if "page_tbl" in io.cache:
            # paged contract: k/v are the whole shared page pool
            # [L, P, ps, KV, hd], carried through the layer scan, and
            # `layer` is this layer's global index. The B new tokens are
            # scattered in place at [layer, page, off]; the row's ring is
            # then gathered through its page table, after the write, so
            # a token attends to itself. Writes from dead/unallocated
            # rows land on the trash page (page 0) and are masked out
            # via k_pos == -1. The pool keeps its sharding on the carry.
            kc, vc = io.cache["k"], io.cache["v"]
            l = io.cache["layer"]
            page, off = io.cache["page"], io.cache["off"]      # [B] int32
            tbl = io.cache["page_tbl"]                         # [B, n]
            pool_axes = ("layer", "pages", "seq", "act_kv", None)
            kc = lc(kc.at[l, page, off].set(k_new[:, 0]), *pool_axes)
            vc = lc(vc.at[l, page, off].set(v_new[:, 0]), *pool_axes)
            B, n = tbl.shape
            ring = (B, n * kc.shape[2]) + kc.shape[3:]         # [B, W, KV, hd]
            ctx = decode_attention(q, kc[l, tbl].reshape(ring),
                                   vc[l, tbl].reshape(ring),
                                   io.q_pos, io.k_pos, cfg, engine)
        else:
            kc, vc = io.cache["k"], io.cache["v"]              # [B, W, KV, hd]
            B = kc.shape[0]
            slot = io.cache["slot"]                            # [B] int32
            rows = jnp.arange(B)
            kc = kc.at[rows, slot].set(k_new[:, 0])
            vc = vc.at[rows, slot].set(v_new[:, 0])
            ctx = decode_attention(q, kc, vc, io.q_pos, io.k_pos, cfg, engine)
        new_cache = {"k": kc, "v": vc}
    else:
        q, k, v = _qkv(p, xn, io.positions, cfg)
        if io.cache is not None and "k_pre" in io.cache:
            # prefix-cached prefill: suffix queries attend over the
            # shared prefix k/v (gathered from the page pool, identical
            # for every row) followed by this row's own suffix keys.
            kp, vp = io.cache["k_pre"], io.cache["v_pre"]      # [Lp, KV, hd]
            B = k.shape[0]
            full = lambda pre, own: jnp.concatenate(
                [jnp.broadcast_to(pre[None].astype(own.dtype),
                                  (B,) + pre.shape), own], axis=1)
            ctx = flash_attention(q, full(kp, k), full(vp, v),
                                  io.q_pos, io.k_pos, cfg, engine)
        else:
            ctx = flash_attention(q, k, v, io.q_pos, io.k_pos, cfg, engine)
        if io.mode == "prefill":
            new_cache = {"k": k, "v": v}
    return attention_out(p, ctx, cfg), new_cache


def apply_block(p, x, io: BlockIO, cfg: ModelConfig, engine):
    """Returns (x_out, new_cache_dict, aux_loss_increment)."""
    aux = 0.0
    new_cache: dict[str, Any] = {}
    xn = apply_norm(p["ln1"], x, cfg)

    if cfg.use_mamba:
        cs = io.cache.get("conv") if io.cache else None
        ss = io.cache.get("ssm") if io.cache else None
        out, cs, ss = apply_mamba(p["mamba"], xn, cfg, engine, cs, ss)
        if io.mode in ("decode", "prefill"):
            new_cache.update({"conv": cs, "ssm": ss})
        x = x + out
    elif cfg.parallel_mamba:
        attn_out, ac = _attn_branch(p["attn"], xn, io, cfg, engine)
        cs = io.cache.get("conv") if io.cache else None
        ss = io.cache.get("ssm") if io.cache else None
        mamba_out, cs, ss = apply_mamba(p["mamba"], xn, cfg, engine, cs, ss)
        if io.mode in ("decode", "prefill"):
            new_cache.update(ac)
            new_cache.update({"conv": cs, "ssm": ss})
        # hymba: mean of per-branch normalized outputs
        fused = 0.5 * (apply_norm(p["ln_attn_out"], attn_out, cfg)
                       + apply_norm(p["ln_mamba_out"], mamba_out, cfg))
        x = x + fused
    else:
        attn_out, ac = _attn_branch(p["attn"], xn, io, cfg, engine)
        new_cache.update(ac)
        x = x + attn_out

    if cfg.has_ffn:
        xn2 = apply_norm(p["ln2"], x, cfg)
        if cfg.n_experts > 0:
            ffn_out, aux = apply_moe(p["ffn"], xn2, cfg, engine)
        else:
            ffn_out = apply_mlp(p["ffn"], xn2, cfg, engine)
        x = x + ffn_out

    x = lc(x, "batch", "seq", "act_embed")
    return x, new_cache, aux
