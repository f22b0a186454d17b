"""Plain float32 reference of a dense decoder with a gated FFN, written
from the published descriptions and the configuration file alone.

    x = embed[tokens]
    per layer:  h = norm(x); q, k, v = h Wq, h Wk, h Wv
                (qk_norm: RMSNorm over head_dim on q and k), RoPE on q, k
                causal softmax(q k^T / sqrt(head_dim)) v, grouped-query
                heads (head i reads kv head i // (heads / kv_heads))
                x += attn Wo
                h = norm(x); x += (silu(h Wg) * (h Wu)) Wd
    logits = norm(x) W_head

``norm`` is RMSNorm with a learned scale ("rmsnorm") or LayerNorm without
scale or bias ("layernorm_np"), both at eps 1e-6. silu is the
configuration's activation: x * sigmoid(x) with sigmoid(x) =
(1 + tanh(x / 2)) / 2, and tanh the uniform cubic Catmull-Rom spline of
``depth`` segments on [0, x_max) through tanh's own knots, odd-extended
and saturated at tanh(x_max) (paper, Eq. 2 and 3).

It imports nothing of the program and takes no table from it. Weights
are the benchmark's own (``chipbench.weights``), widened to float32;
matrix products run at ``highest`` precision. ``control=True`` computes
every weight product with both operands rounded to float8 (e4m3, one
scale per output channel of the weight and per row of the activation):
the lower precision that a later change could be tempted by.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
F8_MAX = 448.0


def spline_windows(x_max: float, depth: int) -> np.ndarray:
    """[depth, 4] control points of each segment: tanh at knots
    (k - 1) * period .. (k + 2) * period for segment k."""
    period = x_max / depth
    knots = np.tanh(np.arange(-1, depth + 3, dtype=np.float64) * period)
    idx = np.arange(depth)[:, None] + np.arange(4)[None, :]
    return knots[idx].astype(np.float32)


def tanh_cr(v, windows, x_max: float, depth: int):
    period = x_max / depth
    av = jnp.abs(v)
    u = av / period
    k = jnp.clip(jnp.floor(u), 0, depth - 1)
    t = u - k
    p = jnp.asarray(windows)[k.astype(jnp.int32)]            # [..., 4]
    t2, t3 = t * t, t * t * t
    w = jnp.stack([-t3 + 2 * t2 - t, 3 * t3 - 5 * t2 + 2,
                   -3 * t3 + 4 * t2 + t, t3 - t2], axis=-1) * 0.5
    y = jnp.sum(p * w, axis=-1)
    y = jnp.where(av >= x_max, jnp.float32(math.tanh(x_max)), y)
    return jnp.where(v < 0, -y, y)


def _f8(x, axis):
    """x rounded to float8 e4m3 with one scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control: bool):
    """x [S, in] @ w [in, out...] at highest precision; under ``control``
    both operands rounded to float8 first."""
    w2 = w.reshape(w.shape[0], -1)
    if control:
        x, w2 = _f8(x, -1), _f8(w2, 0)
    return jnp.matmul(x, w2, precision=HI).reshape((x.shape[0],) + w.shape[1:])


def _norm(x, scale, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("conf_key", "control"))
def hidden(w, tokens, *, conf_key, control=False):
    """Final-norm hidden states [S, d] of one sequence ``tokens`` [S]."""
    conf = dict(conf_key)
    kind, theta = conf["norm"], conf["rope_theta"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    G = H // KV
    windows = spline_windows(conf["x_max"], conf["depth"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    emb = _f8(w["embed"], -1) if control else w["embed"]
    x = emb[tokens]

    def layer(x, p):
        a = p["attn"]
        h = _norm(x, p["ln1"].get("scale"), kind)
        q, k, v = _mm(h, a["wq"], control), _mm(h, a["wk"], control), \
            _mm(h, a["wv"], control)                          # [S, heads, hd]
        if conf["qk_norm"]:
            q = _norm(q, a["q_norm"], "rmsnorm")
            k = _norm(k, a["k_norm"], "rmsnorm")
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhx,khx->hqk", q, k, precision=HI) / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khx->qhx", jax.nn.softmax(s, -1), v, precision=HI)
        x = x + _mm(o.reshape(S, -1), a["wo"].reshape(-1, a["wo"].shape[-1]),
                    control)
        f = p["ffn"]
        h = _norm(x, p["ln2"].get("scale"), kind)
        g = _mm(h, f["w_gate"], control)
        act = g * 0.5 * (1.0 + tanh_cr(g * 0.5, windows, conf["x_max"],
                                       conf["depth"]))
        x = x + _mm(act * _mm(h, f["w_up"], control), f["w_down"], control)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return _norm(x, w["ln_f"].get("scale"), kind)


@functools.partial(jax.jit, static_argnames=("control",))
def logits_at(w, h, rows, *, control=False):
    """Logits [n, V] at hidden rows ``rows``."""
    hr = h[rows]
    head = w["lm_head"]
    if control:
        hr, head = _f8(hr, -1), _f8(head, 0)
    return jnp.matmul(hr, head, precision=HI)


def conf_key(conf: dict) -> tuple:
    """The hashable part of a configuration file the reference reads."""
    act = conf["activation"]
    return (("norm", conf["norm"]), ("rope_theta", float(conf["rope_theta"])),
            ("num_attention_heads", conf["num_attention_heads"]),
            ("num_key_value_heads", conf["num_key_value_heads"]),
            ("qk_norm", bool(conf["qk_norm"])),
            ("x_max", float(act["x_max"])), ("depth", int(act["depth"])))


# keys by which a configuration file states a mechanism this reference
# does not compute, with the value that says it is absent
NOT_DENSE = {"num_local_experts": (0, 1), "num_experts": (0, 1),
             "shared_expert_intermediate_size": (0,),
             "sliding_window": (None,), "attention_bias": (False,),
             "mamba_d_state": (0,), "mamba_d_conv": (0,), "mamba_expand": (0,),
             "mamba_dt_rank": (0,), "layer_types": (None,),
             "attn_layer_period": (None, 1), "gated_ffn": (True,),
             "num_codebooks": (1,)}


def check_shapes(w, conf: dict):
    """Refuses a configuration that states a mechanism outside this
    reference (experts, a window, a bias, Mamba layers, a layer
    pattern, an FFN without a gate, several codebooks), and a weight tree with any leaf but the dense decoder's,
    or with one of another shape than the file's sizes give."""
    stated = {k: conf[k] for k, absent in NOT_DENSE.items()
              if k in conf and conf[k] not in absent}
    if stated:
        raise ValueError(f"the dense reference does not compute what the "
                         f"configuration states: {stated}")
    d, f, L = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    want = {("blocks", "attn", "wq"): (L, d, H, hd),
            ("blocks", "attn", "wk"): (L, d, KV, hd),
            ("blocks", "attn", "wv"): (L, d, KV, hd),
            ("blocks", "attn", "wo"): (L, H, hd, d),
            ("blocks", "ffn", "w_gate"): (L, d, f),
            ("blocks", "ffn", "w_up"): (L, d, f),
            ("blocks", "ffn", "w_down"): (L, f, d),
            ("embed",): None, ("lm_head",): None}
    if conf["qk_norm"]:
        want.update({("blocks", "attn", "q_norm"): (L, hd),
                     ("blocks", "attn", "k_norm"): (L, hd)})
    if conf["norm"] == "rmsnorm":
        want.update({("blocks", "ln1", "scale"): (L, d),
                     ("blocks", "ln2", "scale"): (L, d),
                     ("ln_f", "scale"): (d,)})
    have = {tuple(str(getattr(k, "key", k)) for k in p): tuple(a.shape)
            for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    extra = sorted("/".join(p) for p in set(have) - set(want))
    missing = sorted("/".join(p) for p in set(want) - set(have))
    if extra or missing:
        raise ValueError(f"the dense reference computes no leaf {extra} "
                         f"and lacks {missing}")
    for path, shape in want.items():
        if shape is not None and have[path] != shape:
            raise ValueError(f"reference weights {'/'.join(path)} have shape "
                             f"{have[path]}, the configuration says {shape}")
    V = conf["vocab_size"]
    if have[("lm_head",)][0] != d or have[("lm_head",)][1] < V \
            or have[("embed",)][1] != d or have[("embed",)][0] < V:
        raise ValueError(f"embed {have[('embed',)]} / lm_head "
                         f"{have[('lm_head',)]} do not fit d={d}, V={V}")
