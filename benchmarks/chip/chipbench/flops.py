"""Operation and byte counts, from shapes alone.

The model's counts follow the configuration file (Hugging Face keys);
the kernel's follow the shapes of each call the engine issued. Nothing
here reads the program.
"""
from __future__ import annotations


# a layer's kind, by the name a configuration file gives it in
# ``layer_types``: attention over the whole context, attention over the
# last ``sliding_window`` keys, a Mamba-1 mixer, or both side by side
# (Hymba's parallel heads, their attention windowed where a window is
# stated)
KINDS = {"full_attention": "full", "sliding_attention": "sliding",
         "mamba": "mamba", "hybrid": "hybrid"}


def layer_kinds(c: dict) -> list[str]:
    """The kind of each layer: from ``layer_types`` where the file states
    it; else from ``attn_layer_period`` / ``attn_layer_offset`` (the
    other layers Mamba, as in Jamba); else every layer Mamba in a model
    without attention heads, and attention in any other (windowed where
    the file states ``sliding_window``)."""
    L = c["num_hidden_layers"]
    if c.get("layer_types"):
        kinds = [KINDS[t] for t in c["layer_types"]]
        if len(kinds) != L:
            raise ValueError(f"layer_types holds {len(kinds)} layers, "
                             f"num_hidden_layers says {L}")
        return kinds
    attn = "sliding" if c.get("sliding_window") else "full"
    if c.get("attn_layer_period"):
        p, o = c["attn_layer_period"], c.get("attn_layer_offset", 0)
        return [attn if i % p == o else "mamba" for i in range(L)]
    return ["mamba" if not c["num_attention_heads"] else attn] * L


def _experts(c: dict) -> int:
    """Routed experts of every layer's FFN; 0 for a dense FFN (one expert
    routed top-1 is one)."""
    e = c.get("num_local_experts", c.get("num_experts", 0)) or 0
    return e if e > 1 else 0


def _mamba(c: dict) -> tuple[int, int, int, int]:
    """(d_inner, d_state, dt_rank, d_conv) of a Mamba-1 mixer."""
    return (int(c["mamba_expand"] * c["hidden_size"]), c["mamba_d_state"],
            c["mamba_dt_rank"], c["mamba_d_conv"])


def layer_matmul_params(c: dict, kind: str = "full", experts: int = 0) -> int:
    """Weights one token multiplies through in one decoder layer of
    ``kind``, with its FFN (a top-k MoE with its router, and a shared
    expert where stated, when ``experts``): gated (gate, up, down) unless
    the file states ``gated_ffn`` false (up, down)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    mats = 3 if c.get("gated_ffn", True) else 2
    n = 0
    if kind != "mamba":
        h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
        n += d * h * hd + 2 * d * kv * hd + h * hd * d
    if kind in ("mamba", "hybrid"):
        di, N, R, _ = _mamba(c)
        n += d * 2 * di + di * (R + 2 * N) + R * di + di * d
    if experts:
        n += (c["num_experts_per_tok"] * mats * d * f + d * experts
              + mats * d * c.get("shared_expert_intermediate_size", 0))
    else:
        n += mats * d * f
    return n


def mamba_token_flops(c: dict) -> float:
    """FLOPs of one token through a Mamba-1 mixer outside its matmuls:
    the depthwise causal conv (2 x d_conv per channel), and the selective
    scan at 6 per (d_inner, d_state) element: the state update
    h = exp(dt A) h + (dt x) B (the products dt A, by h and by B, and the
    add: 4) and the read y = sum_n h C (a multiply-add: 2)."""
    di, N, _, K = _mamba(c)
    return 2.0 * K * di + 6.0 * di * N


def _ctx_sum(start: int, n: int, window: int | None) -> float:
    """Sum over positions p = start .. start+n-1 of the keys each token
    attends to: p + 1, or at most ``window``."""
    full = n * (start + 1) + n * (n - 1) / 2.0
    if not window or start + n <= window:
        return full
    k = max(0, window - start)           # tokens that still see all keys
    return k * (start + 1) + k * (k - 1) / 2.0 + (n - k) * float(window)


def span_flops(c: dict, start: int, n: int) -> float:
    """Decoder FLOPs of ``n`` consecutive tokens at positions
    start .. start+n-1 (token at position p attends to p+1 keys, or to
    the last ``sliding_window`` on a windowed layer): 2 per multiply-add
    of every weight, plus QK^T and PV over the context, plus a Mamba
    mixer's conv and scan. The output head is counted apart
    (``head_flops``), because only tokens whose logits are read pay it."""
    if n <= 0:
        return 0.0
    h, hd = c["num_attention_heads"], c["head_dim"]
    experts = _experts(c)
    kinds = layer_kinds(c)
    total = 0.0
    for kind in sorted(set(kinds)):
        L = kinds.count(kind)
        total += 2.0 * L * layer_matmul_params(c, kind, experts) * n
        if kind != "mamba":
            window = c.get("sliding_window") if kind != "full" else None
            total += L * 4.0 * h * hd * _ctx_sum(start, n, window)
        if kind in ("mamba", "hybrid"):
            total += L * mamba_token_flops(c) * n
    return total


def token_flops(c: dict, context: int) -> float:
    """Forward FLOPs of one token at position ``context - 1``."""
    return span_flops(c, context - 1, 1)


def head_flops(c: dict) -> float:
    """FLOPs of one token's logits: one head per codebook."""
    return 2.0 * c["hidden_size"] * c["vocab_size"] * c.get("num_codebooks", 1)


def glu_call(m: int, k: int, n: int, bytes_per_el: int = 2):
    """(flops, bytes) of one fused GLU call out[m, n] =
    act(x[m, k] @ w_gate[k, n]) * (x @ w_up[k, n]): two matmuls, and each
    operand read or written once (the least the call can move)."""
    flops = 4.0 * m * k * n
    nbytes = float(bytes_per_el) * (m * k + 2 * k * n + m * n)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks.bf16_flops_per_s
    tm = nbytes / peaks.hbm_bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
