"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn
from the seed and holding the longest one, is run through the plain
float32 reference: each prompt followed by the tokens the engine served.
For every served token the reference's logits give the gap by which
that token lies below the reference's best token at that position. The
widest gap over the sample is compared with the configuration's limit.
The engine decodes greedily, so a served token is its own best; a gap
well above rounding means it served a token the model does not rank
first.
"""
from __future__ import annotations

import importlib.util

import numpy as np

from .files import ROOT


def reference_module(conf: dict, root=ROOT):
    path = root / "reference" / f"{conf['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + conf["reference"].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: list, seed: int, n: int) -> list:
    """The longest finished request (prompt plus served tokens) and
    ``n - 1`` others drawn from the seed, in uid order."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.prompt_len + len(r.tokens),
                                           r.uid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    k = min(max(n - 1, 0), len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return sorted([longest] + [rest[i] for i in pick], key=lambda r: r.uid)


def bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def gaps(ref, w, conf: dict, reqs: list, *, control: bool = False,
         seq_bucket: int = 512, max_seq: int | None = None):
    """Per request: (served tokens, widest gap of a served token below the
    reference's best), and under ``control`` also the widest gap of the
    token the float8 control ranks first. Sequences are padded to a
    power-of-two bucket (at most ``max_seq``) so that few shapes
    compile; padding follows every real token, so causal attention keeps
    it out of every position read."""
    import jax.numpy as jnp
    key = ref.conf_key(conf)
    out = []
    for r in reqs:
        toks = [int(t) for t in r.tokens]
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(toks[:-1], np.int32)])
        S = len(seq)
        Sp = bucket(S, seq_bucket)
        if max_seq is not None:
            Sp = max(min(Sp, max_seq), S)
        padded = np.zeros(Sp, np.int32)
        padded[:S] = seq
        n = len(toks)
        npad = bucket(n, 16)
        rows = np.zeros(npad, np.int32)
        rows[:n] = np.arange(r.prompt_len - 1, r.prompt_len - 1 + n)
        h = ref.hidden(w, jnp.asarray(padded), conf_key=key)
        lg = np.asarray(ref.logits_at(w, h, jnp.asarray(rows)))[:n]
        best = lg.max(-1)
        served = np.asarray(toks)
        if not (np.isfinite(lg).all() and (served >= 0).all()
                and (served < lg.shape[1]).all()):
            out.append({"uid": r.uid, "tokens": n, "gap": float("inf")})
            continue
        gap = best - lg[np.arange(n), served]
        row = {"uid": r.uid, "tokens": n, "gap": float(gap.max()),
               "agree": int((gap == 0).sum())}
        if control:
            hc = ref.hidden(w, jnp.asarray(padded), conf_key=key, control=True)
            lc = np.asarray(ref.logits_at(w, hc, jnp.asarray(rows),
                                          control=True))[:n]
            choice = lc.argmax(-1)
            row["control_gap"] = float((best - lg[np.arange(n), choice]).max())
        out.append(row)
    return out
