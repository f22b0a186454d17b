"""Plain float32 reference of a decoder with sliding-window attention and
a top-k mixture of gated-FFN experts (Mixtral, arXiv:2401.04088),
written from the published description and the configuration file:

    per layer:  h = rmsnorm(x); q, k, v = h Wq, h Wk, h Wv; RoPE on q, k
                softmax(q k^T / sqrt(head_dim)) v over the keys j with
                i - sliding_window < j <= i (grouped-query heads)
                x += attn Wo
                h = rmsnorm(x); p = softmax(h W_router) over the experts
                the num_experts_per_tok largest p, renormalized to sum 1
                x += sum over those experts e of
                     p_e (silu(h Wg[e]) * (h Wu[e])) Wd[e]
    logits = rmsnorm(x) W_head

silu, the norms, RoPE, the float8 control and the head are the dense
reference's (``dense.py`` beside this file). Every expert is computed
for every token and weighted by zero where it is not chosen: plain, and
small enough at the sizes a test runs.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "chipbench_reference_dense_for_moe", Path(__file__).with_name("dense.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

HI = dense.HI
logits_at = dense.logits_at


@functools.partial(jax.jit, static_argnames=("conf_key", "control"))
def hidden(w, tokens, *, conf_key, control=False):
    """Final-norm hidden states [S, d] of one sequence ``tokens`` [S]."""
    conf = dict(conf_key)
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    W, k_top = conf["sliding_window"], conf["num_experts_per_tok"]
    windows = dense.spline_windows(conf["x_max"], conf["depth"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    diff = pos[:, None] - pos[None, :]
    visible = (diff >= 0) & (diff < W)
    emb = dense._f8(w["embed"], -1) if control else w["embed"]
    x = emb[tokens]
    mm = functools.partial(dense._mm, control=control)

    def silu(g):
        return g * 0.5 * (1.0 + dense.tanh_cr(g * 0.5, windows, conf["x_max"],
                                              conf["depth"]))

    def layer(x, p):
        a = p["attn"]
        h = dense._norm(x, p["ln1"]["scale"], "rmsnorm")
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        q = dense._rope(q, pos, conf["rope_theta"])
        k = dense._rope(k, pos, conf["rope_theta"])
        k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhx,khx->hqk", q, k, precision=HI) / math.sqrt(q.shape[-1])
        s = jnp.where(visible[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khx->qhx", jax.nn.softmax(s, -1), v, precision=HI)
        x = x + mm(o.reshape(S, -1), a["wo"].reshape(-1, a["wo"].shape[-1]))
        f = p["ffn"]
        h = dense._norm(x, p["ln2"]["scale"], "rmsnorm")
        probs = jax.nn.softmax(jnp.matmul(h, f["router"], precision=HI), -1)
        top_p, top_i = jax.lax.top_k(probs, k_top)
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        gate_w = jnp.zeros_like(probs).at[pos[:, None], top_i].set(top_p)
        y = 0.0
        for e in range(f["router"].shape[-1]):
            act = silu(mm(h, f["w_gate"][e])) * mm(h, f["w_up"][e])
            y = y + gate_w[:, e:e + 1] * mm(act, f["w_down"][e])
        return x + y, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return dense._norm(x, w["ln_f"]["scale"], "rmsnorm")


def conf_key(conf: dict) -> tuple:
    """The hashable part of a configuration file the reference reads."""
    act = conf["activation"]
    return (("rope_theta", float(conf["rope_theta"])),
            ("num_attention_heads", conf["num_attention_heads"]),
            ("num_key_value_heads", conf["num_key_value_heads"]),
            ("sliding_window", int(conf["sliding_window"])),
            ("num_experts_per_tok", int(conf["num_experts_per_tok"])),
            ("x_max", float(act["x_max"])), ("depth", int(act["depth"])))


def check_shapes(w, conf: dict):
    """Refuses a tree with another leaf set or shape than this reference
    computes."""
    d, f, L = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    H, KV, hd, E = (conf["num_attention_heads"], conf["num_key_value_heads"],
                    conf["head_dim"], conf["num_local_experts"])
    want = {"blocks/attn/wq": (L, d, H, hd), "blocks/attn/wk": (L, d, KV, hd),
            "blocks/attn/wv": (L, d, KV, hd), "blocks/attn/wo": (L, H, hd, d),
            "blocks/ffn/router": (L, d, E), "blocks/ffn/w_gate": (L, E, d, f),
            "blocks/ffn/w_up": (L, E, d, f), "blocks/ffn/w_down": (L, E, f, d),
            "blocks/ln1/scale": (L, d), "blocks/ln2/scale": (L, d),
            "ln_f/scale": (d,)}
    have = {"/".join(str(getattr(k, "key", k)) for k in p): tuple(a.shape)
            for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    have.pop("embed", None)
    have.pop("lm_head", None)
    if have != want:
        raise ValueError(f"MoE reference: weights {have}, want {want}")
