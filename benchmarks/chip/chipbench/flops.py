"""Operation and byte counts, from shapes alone.

The model's counts follow the configuration file (Hugging Face keys);
the kernel's follow the shapes of each call the engine issued. Nothing
here reads the program.
"""
from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = 3 * d * f                      # gated FFN: gate, up, down
    return attn + ffn


def token_flops(c: dict, context: int) -> float:
    """Forward FLOPs of one token through the decoder stack, attending to
    ``context`` keys (itself included): 2 per multiply-add of every
    weight, plus QK^T and PV over the context. The output head is counted
    apart (``head_flops``), because only tokens whose logits are read pay
    it."""
    L = c["num_hidden_layers"]
    h, hd = c["num_attention_heads"], c["head_dim"]
    return 2.0 * L * layer_matmul_params(c) + L * 4.0 * h * hd * context


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def span_flops(c: dict, start: int, n: int) -> float:
    """Decoder FLOPs of ``n`` consecutive tokens at positions
    start .. start+n-1 (token at position p attends to p+1 keys)."""
    if n <= 0:
        return 0.0
    L = c["num_hidden_layers"]
    h, hd = c["num_attention_heads"], c["head_dim"]
    ctx_sum = n * (start + 1) + n * (n - 1) / 2.0
    return 2.0 * L * layer_matmul_params(c) * n + L * 4.0 * h * hd * ctx_sum


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
