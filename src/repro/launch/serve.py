"""Batched serving launcher on the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 64 --gen 32

`serve_batch` is a thin compatibility wrapper over `repro.serve`'s
ServeEngine: prompts become engine requests, decode runs as in-jit
`lax.scan` chunks with on-device sampling, and the returned tokens/stats
match the old lockstep contract. With `--model-parallel N` the engine's
whole datapath (batched prefill, slot insert, decode chunks) runs under
explicit NamedShardings on the mesh. EVERY workload goes through the
engine — multi-codebook archs (musicgen) decode [.., K] codebook planes
inside the same schedules. The per-token lockstep loop survives only as
`_serve_batch_python`, the benchmark-only reference the engine's token
identity and speedups are measured against (benchmarks/serve_bench.py);
it is not a serving path.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.data import DataConfig, SyntheticPipeline
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.parallel import partition as part
from repro.serve import (AutoscaleConfig, EngineConfig, InProcessReplica,
                         Router, RouterConfig, ServeEngine, sample_tokens)


@dataclasses.dataclass
class ServeStats:
    prefill_s: float
    decode_s: float
    n_prompts: int
    prompt_len: int         # longest prompt of the batch
    generated: int          # tokens emitted per prompt (incl. prefill sample)
    decode_steps: int       # sequential decode steps actually run
    decode_tokens: int      # PLANE tokens emitted by decode steps: a
                            # multi-codebook position counts K (matches
                            # EngineStats' accounting, so the engine and
                            # the lockstep reference agree exactly)
    planes: int = 1         # codebook count K of the served arch

    @property
    def prefill_tokens_per_s(self):
        # a sub-resolution prefill (or a path that skipped it) leaves
        # prefill_s exactly 0.0 — mirror the decode guard, don't divide
        if not self.prefill_s:
            return 0.0
        return self.n_prompts * self.prompt_len * self.planes / self.prefill_s

    @property
    def decode_tokens_per_s(self):
        # gen=1 workloads run zero decode steps (first token comes from
        # the prefill logits), leaving decode_s exactly 0.0
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0


def _mask_after_eos(tokens: np.ndarray, eos_id: int) -> np.ndarray:
    """Right-pad each row with 0 after its first `eos_id` (the eos itself
    is kept) — the engine's ragged-completion contract. One vectorized
    cumsum-mask expression, no per-row host loop. tokens [B, gen] or
    [B, gen, K]; K > 1 tests the eos on codebook 0 (the engine's
    multi-codebook contract) and zeroes whole [K] positions."""
    head = tokens[..., 0] if tokens.ndim == 3 else tokens        # [B, gen]
    is_eos = head == eos_id
    seen = np.cumsum(is_eos, axis=1)
    keep = (seen == 0) | (is_eos & (seen == 1))   # up to & incl. first eos
    if tokens.ndim == 3:
        keep = keep[..., None]
    return np.where(keep, tokens, 0).astype(tokens.dtype)


def _serve_batch_python(cfg, params, prompts, gen_tokens: int, *,
                        temperature: float = 0.0, seed: int = 0,
                        capacity: int | None = None,
                        eos_id: int | None = None):
    """BENCHMARK-ONLY lockstep reference — not a serving path (serving
    always goes through ServeEngine, serve_batch below). One jitted
    decode dispatch + host sync per token; the baseline the engine's
    token identity and speedups are measured against
    (benchmarks/serve_bench.py, tests/test_serve_multicodebook.py).

    Exactly gen_tokens - 1 decode steps run (the first token is sampled
    from the prefill logits; no trailing wasted step). With `eos_id`,
    rows are right-padded with 0 after their first eos (codebook 0 for
    K > 1) — token-identical (greedy) to the engine's early-stop, though
    the lockstep loop still runs the full gen_tokens steps."""
    B, S = prompts.shape[0], prompts.shape[1]
    capacity = capacity or M.cache_capacity(cfg, S + gen_tokens)
    prefill = jax.jit(steps_mod.make_prefill_step(cfg, capacity=capacity))
    decode = jax.jit(steps_mod.make_serve_step(cfg), donate_argnums=(2,))
    temp = jnp.full((B,), temperature, jnp.float32)

    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompts})
    logits = jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    # fold the key before first use: sampling with the root key and then
    # feeding the same key to split() would correlate the first sample
    # with the rest of the stream
    key = jax.random.key(seed)
    key, sub = jax.random.split(key)
    multi = cfg.n_codebooks > 1
    tok = sample_tokens(sub, logits, temp)                 # [B(, K)]
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(gen_tokens - 1):
        step_tok = tok[:, None] if not multi else tok[:, None, :]
        key, sub = jax.random.split(key)
        logits, cache = decode(params, {"tokens": step_tok}, cache)
        tok = sample_tokens(sub, logits, temp)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    tokens = jnp.stack(out, axis=1)                        # [B, gen(, K)]
    if eos_id is not None:
        tokens = jnp.asarray(_mask_after_eos(np.asarray(tokens), eos_id))
    K = cfg.n_codebooks
    return tokens, ServeStats(t_prefill, t_decode, B, S, gen_tokens,
                              decode_steps=gen_tokens - 1,
                              decode_tokens=B * (gen_tokens - 1) * K,
                              planes=K)


def serve_batch(cfg, params, prompts, gen_tokens: int, *,
                temperature: float = 0.0, seed: int = 0,
                capacity: int | None = None,
                slots: int | None = None, chunk: int = 8,
                eos_id: int | None = None, mesh=None,
                rules: dict | None = None, cache: str = "paged",
                page_size: int = 16, prefix_cache: bool = True,
                chunk_prefill: int = 0, token_budget: int | None = None):
    """prompts: int32 [B, S(, K)], or a sequence of B prompts of their
    own lengths (S is then the longest). Returns (tokens [B, gen(, K)],
    stats).

    Always constructs a continuous-batching ServeEngine (batched-bucket
    admission, in-jit scan decode; `mesh` shards its datapath;
    `chunk_prefill`/`token_budget` select its token-budget schedule) —
    multi-codebook archs included: their [B, S, K] prompts decode as
    K-plane streams through the same engine. An explicit `capacity`
    overrides the engine's default S + gen_tokens cache sizing (it must
    still fit every request).

    With `eos_id`, rows that emit it (codebook 0 for K > 1) stop early;
    every returned row is right-padded with 0 to gen_tokens, so
    completions of ragged lengths still stack into one block."""
    B, S = len(prompts), max(len(p) for p in prompts)
    max_len = S + gen_tokens
    if capacity is not None:
        # an earlier version silently rerouted any explicit capacity to
        # the python loop (losing batching AND the mesh); the engine
        # sizes per-slot rings itself, so honor it as max_len instead
        if capacity < max_len:
            raise ValueError(
                f"capacity {capacity} < prompt_len + gen_tokens "
                f"({S} + {gen_tokens}): requests could not finish")
        max_len = capacity
    ecfg = EngineConfig(slots=slots or B, max_prompt_len=S,
                        max_len=max_len,
                        chunk=max(1, min(chunk, gen_tokens - 1) or 1),
                        cache=cache, page_size=page_size,
                        prefix_cache=prefix_cache,
                        chunk_prefill=chunk_prefill,
                        token_budget=token_budget, seed=seed)
    engine = ServeEngine(cfg, params, ecfg, mesh=mesh, rules=rules)
    for b in range(B):
        engine.submit(np.asarray(prompts[b]), gen_tokens,
                      temperature=temperature, eos_id=eos_id)
    done = engine.run()
    K = cfg.n_codebooks
    shape = (B, gen_tokens, K) if K > 1 else (B, gen_tokens)
    rows = np.zeros(shape, np.int32)                       # 0-padded ragged
    for c in done:
        rows[c.uid, :len(c.tokens)] = np.asarray(c.tokens, np.int32)
    tokens = jnp.asarray(rows)                             # [B, gen(, K)]
    st = engine.stats
    return tokens, ServeStats(st.prefill_s, st.decode_s, B, S, gen_tokens,
                              decode_steps=st.decode_steps,
                              decode_tokens=st.decode_tokens, planes=K)


def serve_routed(cfg, params, prompts, gen_tokens: int, *,
                 replicas: int = 2, queue_limit: int = 64,
                 policy: str = "reject", autoscale=None,
                 temperature: float = 0.0, seed: int = 0,
                 slots: int | None = None, chunk: int = 8,
                 eos_id: int | None = None, mesh=None,
                 rules: dict | None = None, **engine_kw):
    """Serve `prompts` through the multi-replica Router: N in-process
    `ServeEngine` replicas (sharing the SAME param arrays — no copies)
    behind load-aware dispatch, a bounded router queue, and optionally
    the stats-driven autoscaler (`autoscale=AutoscaleConfig(...)`).

    Returns (tokens [B, gen], stats, router) — rows the router shed
    under backpressure stay all-zero (their uids appear in
    `router.completions` with finish_reason="shed"); `stats` aggregates
    the surviving fleet's engine counters. Multi-codebook prompts
    [B, S, K] route exactly like scalar streams (replicas are engines)."""
    B, S = prompts.shape[0], prompts.shape[1]
    ecfg = EngineConfig(slots=slots or max(1, B // max(replicas, 1)),
                        max_prompt_len=S, max_len=S + gen_tokens,
                        chunk=max(1, min(chunk, gen_tokens - 1) or 1),
                        seed=seed, **engine_kw)

    def factory(rid):
        return InProcessReplica(
            ServeEngine(cfg, params, ecfg, mesh=mesh, rules=rules))

    router = Router(factory, RouterConfig(
        replicas=replicas, queue_limit=queue_limit, policy=policy,
        autoscale=autoscale))
    for b in range(B):
        router.submit(np.asarray(prompts[b]), gen_tokens,
                      temperature=temperature, eos_id=eos_id)
    done = router.run()
    K = cfg.n_codebooks
    shape = (B, gen_tokens, K) if K > 1 else (B, gen_tokens)
    rows = np.zeros(shape, np.int32)
    for c in done:
        if c.tokens:
            rows[c.uid, :len(c.tokens)] = np.asarray(c.tokens, np.int32)
    st = router.engine_totals()
    stats = ServeStats(st.prefill_s, st.decode_s, B, S, gen_tokens,
                       decode_steps=st.decode_steps,
                       decode_tokens=st.decode_tokens, planes=K)
    return jnp.asarray(rows), stats, router


def _parse_autoscale(spec: str | None):
    """--autoscale MIN:MAX -> AutoscaleConfig (None passes through)."""
    if spec is None:
        return None
    try:
        lo, hi = (int(x) for x in spec.split(":"))
    except ValueError:
        raise SystemExit(f"--autoscale wants MIN:MAX, got {spec!r}")
    return AutoscaleConfig(min_replicas=lo, max_replicas=hi)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="qwen3-0.6b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--activation", default=None)
    p.add_argument("--act-impl", default=None,
                   help="approximant scheme override (cr_spline|pwl|poly|"
                        "rational|...) for the serving engine")
    p.add_argument("--act-impl-kernel", action="store_true",
                   help="with --act-impl: use_kernel=True (one pallas_call "
                        "per nonlinearity)")
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--slots", type=int, default=None,
                   help="decode slots (default = batch)")
    p.add_argument("--chunk", type=int, default=8,
                   help="in-jit decode steps per dispatch")
    p.add_argument("--eos-id", type=int, default=None,
                   help="stop rows early on this token id")
    p.add_argument("--cache", choices=("paged", "slot"), default="paged",
                   help="KV cache contract: shared page pool (default) "
                        "or the legacy per-slot rings")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page (--cache paged)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable prefix page sharing (--cache paged)")
    p.add_argument("--chunk-prefill", type=int, default=0,
                   help="prompt tokens per prefill chunk; > 0 switches "
                        "the engine to the token-budget schedule that "
                        "interleaves chunked prefill with decode "
                        "(paged attention archs only)")
    p.add_argument("--token-budget", type=int, default=None,
                   help="token budget per engine iteration (requires "
                        "--chunk-prefill; default slots*chunk + "
                        "chunk_prefill)")
    p.add_argument("--replicas", type=int, default=1,
                   help="> 1: serve through the multi-replica Router "
                        "(in-process engine replicas, load-aware "
                        "dispatch; params shared, no copies)")
    p.add_argument("--router-queue", type=int, default=64,
                   help="bounded router admission queue (backpressure)")
    p.add_argument("--router-policy", choices=("reject", "shed"),
                   default="reject",
                   help="queue-full policy: reject the newcomer or shed "
                        "the oldest queued request")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="enable the stats-driven autoscaler with this "
                        "replica range (implies the router path)")
    p.add_argument("--json", default=None, help="write stats JSON here")
    args = p.parse_args(argv)
    use_compile_cache()

    cfg = registry.get(args.arch, smoke=args.smoke)
    if args.activation:
        cfg = dataclasses.replace(
            cfg, activation=dataclasses.replace(cfg.activation,
                                                impl=args.activation))
    if args.act_impl_kernel and not args.act_impl:
        raise SystemExit("--act-impl-kernel requires --act-impl <scheme>")
    if args.act_impl:
        from repro.configs.common import act_impl_of
        cfg = act_impl_of(cfg, args.act_impl,
                          use_kernel=True if args.act_impl_kernel else None)
    mesh = make_host_mesh(1, args.model_parallel)
    if dict(mesh.shape)["model"] < args.model_parallel:
        devices = jax.devices()
        hint = (" (force host devices via XLA_FLAGS="
                "--xla_force_host_platform_device_count=N)"
                if devices[0].platform == "cpu" else "")
        raise SystemExit(
            f"--model-parallel {args.model_parallel} needs that many "
            f"devices; found {len(devices)} {devices[0].platform} "
            f"device(s) ({devices[0].device_kind}){hint}")
    act_tag = cfg.activation.tag()
    if cfg.act_impl:
        act_tag += f" (act_impl={cfg.act_impl})"
    print(f"[serve] arch={cfg.name} act={act_tag} "
          f"codebooks={cfg.n_codebooks} mesh={dict(mesh.shape)}")

    with part.axis_rules(mesh):
        params, _ = M.materialize_params(cfg, seed=args.seed)
        # serving precision: bf16 weights
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

        pipe = SyntheticPipeline(
            cfg, DataConfig(seed=args.seed,
                            vocab_size=min(cfg.vocab_size, 4096)),
            args.batch, args.prompt_len)
        prompts = pipe(0)["tokens"]
        router = None
        if args.replicas > 1 or args.autoscale:
            tokens, stats, router = serve_routed(
                cfg, params, prompts, args.gen,
                replicas=args.replicas, queue_limit=args.router_queue,
                policy=args.router_policy,
                autoscale=_parse_autoscale(args.autoscale),
                temperature=args.temperature, seed=args.seed,
                slots=args.slots, chunk=args.chunk, eos_id=args.eos_id,
                mesh=mesh, cache=args.cache, page_size=args.page_size,
                prefix_cache=not args.no_prefix_cache,
                chunk_prefill=args.chunk_prefill,
                token_budget=args.token_budget)
        else:
            tokens, stats = serve_batch(
                cfg, params, prompts, args.gen,
                temperature=args.temperature,
                seed=args.seed,
                slots=args.slots, chunk=args.chunk,
                eos_id=args.eos_id, mesh=mesh,
                cache=args.cache,
                page_size=args.page_size,
                prefix_cache=not args.no_prefix_cache,
                chunk_prefill=args.chunk_prefill,
                token_budget=args.token_budget)

    if router is not None:
        rs = router.stats
        print(f"[serve] router: {rs.completed}/{rs.submitted} completed "
              f"(shed {rs.shed}, rejected {rs.rejected}) over "
              f"{len(router.replicas)} replicas "
              f"(peak {rs.replica_peak}, +{rs.scale_ups}/-{rs.scale_downs} "
              f"scale actions)")
    print(f"[serve] prefill {stats.prefill_tokens_per_s:,.0f} tok/s "
          f"({stats.prefill_s*1e3:.0f} ms), decode "
          f"{stats.decode_tokens_per_s:,.0f} tok/s "
          f"({stats.decode_s*1e3:.0f} ms for {stats.decode_steps} steps, "
          f"{args.batch} seqs)")
    print("[serve] sample output tokens:", np.asarray(tokens)[0, :16].tolist())
    if args.json:
        doc = dataclasses.asdict(stats)
        if router is not None:
            doc["router"] = dataclasses.asdict(router.stats)
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
    return stats


if __name__ == "__main__":
    main()
