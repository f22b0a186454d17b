#!/usr/bin/env python3
"""Bring-up check: serve qwen3-0.6b at its full published width on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: TP=4 against TP=1 only

One chip runs three phases on the same 8 seeded requests (prompts of
64, 128, 256 and 512 tokens, two of each, in seeded order and content;
32 new tokens each, greedy) with random bf16 weights made from --seed:

  A          ``launch.serve.serve_batch`` with the default engine: paged
             KV cache, jnp Catmull-Rom activations fused by XLA.
  reference  the lockstep loop ``_serve_batch_python``, one batch per
             prompt length.
  B          ``serve_batch`` under ``fused_of(cfg)``: every GLU FFN runs
             through the ``glu_2d`` Pallas kernel, compiled by Mosaic, and
             the other nonlinearities through ``elementwise_2d``.

Each of A and B also runs one ragged prefill step over all 8 prompts
[8, 512] (the kernel's prefill width: 4096 rows), whose last-token
logits are compared. The checks: every token inside the vocabulary,
every logit finite, A's first token of every request equal to the
reference's unless the reference's own logits hold a near-tie there
(``first_tokens_agree``), B's prefill compiled with the Mosaic kernel in
it (``tpu_custom_call``), and max|logits_A - logits_B| within
``LOGIT_GAP_BOUND`` of max|logits_A|.

``--chips 4`` runs one phase and nothing else: the same requests served
with tensor parallelism over four chips (``make_host_mesh(1, 4)``, the
engine's ``serve_shardings``) against TP=1 on the first of them, with
the same checks and a check that the weights and the KV pages are
sharded.

The seconds printed are set-up timings of this process (compile and
wall time per phase), not benchmark results. The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
checkout. The last line of standard output is one JSON object, printed
only when every check passed; without a TPU the script exits nonzero.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
PROMPT_LENS = (64, 128, 256, 512)   # powers of two: the engine's prefill
                                    # buckets equal the prompts, so A and
                                    # the reference run the same shapes
GEN = 32
# max|logits_A - logits_B| / max|logits_A|. A rounds the gate and up
# projections to bf16 before the activation and the product; B keeps
# them in the kernel's f32 accumulators. That is about one bf16 rounding
# (2^-8) of the FFN output per layer, adding up over 28 residual layers
# to a few percent of the logit scale at the largest element: on a TPU
# v5e, A against B differs by 0.020, and two programs of the very same
# model (A against the lockstep reference, TP=4 against TP=1) by 0.019
# and 0.025. The bound is twice the largest of these; a kernel that reads
# the wrong table row or swaps its operands lands above it.
LOGIT_GAP_BOUND = 0.05
# Two programs of the same model (the engine's prefill and the lockstep
# reference's, or TP=4 and TP=1) round differently in bf16. With random
# weights the top two logits of a request can lie closer than that
# noise, and greedy then picks either: a first token that differs from
# the reference's passes only when both are within TIE_BOUND x
# max|logit| of the reference's best logit (a wrong row or position
# lands several logit units below it).
TIE_BOUND = 0.02

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SetupClock:
    """Wall and compile seconds per phase. Compile seconds sum JAX's
    backend-compile events (tracing and lowering, which nest, are left
    out); a compile served from the persistent cache counts its read
    time and one cache hit."""

    def __init__(self, jax):
        self.compile_s, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, t0 = self.compile_s, self.hits, time.perf_counter()
        yield
        print(f"[setup-timing] {name}: wall_s={time.perf_counter() - t0} "
              f"compile_s={self.compile_s - c0} "
              f"cache_hits={self.hits - h0}", flush=True)


def make_requests(vocab: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.repeat(PROMPT_LENS, 2))
    return [rng.integers(0, vocab, size=int(n), dtype=np.int32)
            for n in lens]


def ragged_batch(prompts):
    import jax.numpy as jnp
    tokens = np.zeros((len(prompts), max(map(len, prompts))), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    return {"tokens": jnp.asarray(tokens),
            "lengths": jnp.asarray([len(p) for p in prompts], jnp.int32)}


def prefill_logits(cfg, params, prompts, mesh=None):
    """Last-token logits [N, V] of one ragged prefill step over all the
    prompts, and the compiled step's text."""
    import jax
    from repro.launch import steps
    from repro.parallel import partition as part
    step = steps.make_prefill_step(cfg)
    batch = ragged_batch(prompts)
    if mesh is None:
        fn = jax.jit(lambda p, b: step(p, b)[0])
    else:
        rules = part.serve_rules()
        psh, _, repl = steps.serve_shardings(
            cfg, len(prompts), batch["tokens"].shape[1], mesh, rules)

        def traced(p, b):
            with part.axis_rules(mesh, rules):
                return step(p, b)[0]
        fn = jax.jit(traced, in_shardings=(psh, repl), out_shardings=repl)
    compiled = fn.lower(params, batch).compile()
    return np.asarray(compiled(params, batch)), compiled.as_text()


def reference_tokens(cfg, params, prompts):
    """Greedy tokens of the lockstep reference, one batch per length,
    and the logits it drew each first token from (its own prefill
    program, run once more)."""
    import jax
    import jax.numpy as jnp
    from repro.launch import steps
    from repro.launch.serve import _serve_batch_python
    from repro.models import model as M
    cap = M.cache_capacity(cfg, max(map(len, prompts)) + GEN)
    prefill = jax.jit(steps.make_prefill_step(cfg, capacity=cap))
    toks, logits = [None] * len(prompts), [None] * len(prompts)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        batch = jnp.asarray(np.stack([prompts[i] for i in idx]))
        out, _ = _serve_batch_python(cfg, params, batch, GEN, capacity=cap)
        first = np.asarray(prefill(params, {"tokens": batch})[0])
        for j, i in enumerate(idx):
            toks[i], logits[i] = np.asarray(out[j]), first[j]
    return np.stack(toks), np.stack(logits)


def serve_engine(cfg, params, prompts, mesh=None):
    """The engine ``serve_batch`` builds, kept so that its placed
    weights and KV pages can be inspected. Returns (tokens, engine)."""
    from repro.serve import EngineConfig, ServeEngine
    S = max(map(len, prompts))
    engine = ServeEngine(cfg, params, EngineConfig(
        slots=len(prompts), max_prompt_len=S, max_len=S + GEN), mesh=mesh)
    for p in prompts:
        engine.submit(p, GEN)
    rows = np.zeros((len(prompts), GEN), np.int32)
    for c in engine.run():
        rows[c.uid, :len(c.tokens)] = c.tokens
    return rows, engine


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail=""):
        print(f"[check] {'PASS' if ok else 'FAIL'} {name} {detail}",
              flush=True)
        if not ok:
            self.failed.append(name)


def check_tokens(check, name, tokens, vocab):
    check(f"{name} tokens in vocab", bool(((tokens >= 0) &
                                           (tokens < vocab)).all()),
          f"shape={tokens.shape}")


def compare_logits(check, name, la, lb):
    finite = bool(np.isfinite(la).all() and np.isfinite(lb).all())
    check(f"{name} logits finite", finite)
    gap, scale = float(np.max(np.abs(la - lb))), float(np.max(np.abs(la)))
    print(f"[result] {name} prefill logits: max_abs_gap={gap} "
          f"max_abs_logit={scale} relative_gap={gap / scale} "
          f"argmax_agree={int((la.argmax(-1) == lb.argmax(-1)).sum())}"
          f"/{len(la)}")
    check(f"{name} logit gap <= {LOGIT_GAP_BOUND} * max|logit|",
          finite and gap <= LOGIT_GAP_BOUND * scale)


def first_tokens_agree(check, name, tok0, ref_tok0, ref_logits):
    """Per request: the two first tokens are equal, or both lie within
    TIE_BOUND x max|logit| of the best of ``ref_logits``."""
    rows = np.arange(len(tok0))
    best = ref_logits.max(-1)
    top2 = np.sort(ref_logits, -1)[:, -2]
    bound = TIE_BOUND * float(np.abs(ref_logits).max())
    deficit = np.maximum(best - ref_logits[rows, tok0],
                         best - ref_logits[rows, ref_tok0])
    ok = (tok0 == ref_tok0) | (deficit <= bound)
    for i in rows:
        print(f"[result] {name} request {i}: first tokens "
              f"{tok0[i]} vs {ref_tok0[i]} top2_margin={best[i] - top2[i]} "
              f"deficit={deficit[i]}")
    check(f"{name} first tokens agree (ties within {bound})",
          bool(ok.all()),
          f"equal {int((tok0 == ref_tok0).sum())}/{len(tok0)}")


def agreement(name, a, b):
    print(f"[result] {name} greedy tokens agree: {int((a == b).sum())}"
          f"/{a.size} (first tokens {int((a[:, 0] == b[:, 0]).sum())}"
          f"/{len(a)})")


def one_chip(jax, cfg, params, prompts, clock, check):
    from repro.configs.common import fused_of
    from repro.launch.serve import serve_batch
    vocab = cfg.vocab_size
    with clock.phase("A default engine serve_batch"):
        tok_a = np.asarray(serve_batch(cfg, params, prompts, GEN)[0])
    print("[tokens] A", tok_a.tolist())
    with clock.phase("reference lockstep"):
        tok_ref, ref_logits = reference_tokens(cfg, params, prompts)
    agreement("A vs reference", tok_a, tok_ref)
    check_tokens(check, "A", tok_a, vocab)
    check_tokens(check, "reference", tok_ref, vocab)
    check("reference first tokens are its logits' argmax",
          bool((ref_logits.argmax(-1) == tok_ref[:, 0]).all()))
    first_tokens_agree(check, "A vs reference", tok_a[:, 0], tok_ref[:, 0],
                       ref_logits)

    fcfg = fused_of(cfg)
    check("fused_of deploys the kernels",
          fcfg.fuse_mlp and fcfg.activation.use_kernel)
    with clock.phase("B fused engine serve_batch"):
        tok_b = np.asarray(serve_batch(fcfg, params, prompts, GEN)[0])
    print("[tokens] B", tok_b.tolist())
    agreement("B vs A", tok_b, tok_a)
    check_tokens(check, "B", tok_b, vocab)

    with clock.phase("prefill logits A"):
        la, _ = prefill_logits(cfg, params, prompts)
    with clock.phase("prefill logits B"):
        lb, text_b = prefill_logits(fcfg, params, prompts)
    check("B prefill holds the compiled kernel (tpu_custom_call)",
          "tpu_custom_call" in text_b)
    # the same model through two programs: the yardstick for A vs B
    compare_logits(check, "reference vs A", ref_logits, la)
    compare_logits(check, "A vs B", la, lb)


def four_chips(jax, cfg, params, prompts, clock, check):
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 4)
    check("mesh is TP=4", dict(mesh.shape) == {"data": 1, "model": 4},
          str(dict(mesh.shape)))
    with clock.phase("TP=4 engine"):
        tok_tp, engine = serve_engine(cfg, params, prompts, mesh=mesh)
    print("[tokens] TP=4", tok_tp.tolist())
    leaves = jax.tree_util.tree_flatten_with_path(engine.params)[0]
    w_gate = next(v for k, v in leaves if "w_gate" in jax.tree_util.keystr(k))
    k_pages = engine.cache["layers"]["k"]
    for name, arr in (("w_gate", w_gate), ("kv pages k", k_pages)):
        shard = arr.addressable_shards[0].data.shape
        print(f"[sharding] {name}: shape={arr.shape} per-device={shard} "
              f"{arr.sharding}")
        check(f"{name} sharded over 4 chips",
              len(arr.sharding.device_set) == 4
              and not arr.sharding.is_fully_replicated)
    for d in jax.devices():
        print(f"[memory] {d}: bytes_in_use="
              f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    del engine
    with clock.phase("TP=1 engine on the first chip"):
        tok_one, _ = serve_engine(cfg, params, prompts)
    agreement("TP=4 vs TP=1", tok_tp, tok_one)
    check_tokens(check, "TP=4", tok_tp, cfg.vocab_size)
    check_tokens(check, "TP=1", tok_one, cfg.vocab_size)
    with clock.phase("prefill logits TP=4 and TP=1"):
        l4, _ = prefill_logits(cfg, params, prompts, mesh=mesh)
        l1, _ = prefill_logits(cfg, params, prompts)
    compare_logits(check, "TP=4 vs TP=1", l1, l4)
    first_tokens_agree(check, "TP=4 vs TP=1", tok_tp[:, 0], tok_one[:, 0], l1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many TPU "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"[device] {devices[0].device_kind} x{len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    from repro.configs import registry
    from repro.models import model as M
    cfg = registry.get(ARCH)
    print(f"[config] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} act={cfg.activation.tag()}")
    clock, check = SetupClock(jax), Checks()
    with clock.phase("build params"):
        params, _ = M.materialize_params(cfg, seed=args.seed)
        # serving precision: bf16 weights, as launch/serve.py:main
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"[config] parameters={n_params}")
    prompts = make_requests(cfg.vocab_size, args.seed)
    print(f"[config] prompt lengths={[len(p) for p in prompts]} gen={GEN}")

    run = four_chips if args.chips == 4 else one_chip
    run(jax, cfg, params, prompts, clock, check)
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
