"""The system under test, and the only module here that imports it.

It builds the program's model configuration named by a configuration
file and checks that it is the configuration the file states, makes its
weights with the benchmark's fill, and builds the serving engine
(``repro.serve.ServeEngine``, the engine ``launch/serve.serve_batch``
builds). Everything else the harness reads from the program goes through
the engine's public surface: ``submit``, ``step``, ``sched.slots``,
``stats`` and ``completions``.
"""
from __future__ import annotations

import itertools
import sys

from . import flops
from .files import REPO

if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked
    against every size and mechanism the file states.

    Keys of the file are read by the program attribute they name (the
    map below). A mechanism's key that the file leaves out takes its
    dense value (no experts, no window, no bias, no Mamba layers, a gated
    FFN, one codebook), so a
    program that has the mechanism fails under a file that does not
    state it. ``rope_theta`` is compared only where the program rotates
    (a RoPE-less program reads None, so its file leaves the key out).
    The file's layer pattern (``flops.layer_kinds``, which the FLOP count
    follows) is compared with the one the program runs
    (``program_layer_kinds``). ``program.fields`` ({ModelConfig
    attribute: value}) names any further attribute to hold to a value,
    read by ``getattr``."""
    from repro.configs import registry
    from repro.configs.common import fused_of
    prog = conf["program"]
    cfg = registry.get(prog["arch"], smoke=bool(prog.get("smoke", False)))
    if prog.get("fused"):
        cfg = fused_of(cfg)
    act = conf["activation"]
    moe = cfg.n_experts > 0
    kinds = program_layer_kinds(cfg)
    mamba = any(k in ("mamba", "hybrid") for k in kinds)
    have = {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim_,
        "vocab_size": cfg.vocab_size,
        "rope_theta": None if cfg.rope_kind == "none" else cfg.rope_theta,
        "norm": cfg.norm, "qk_norm": cfg.qk_norm,
        "hidden_act": cfg.mlp_act, "compute_dtype": cfg.compute_dtype,
        "activation.impl": cfg.activation.impl,
        "activation.depth": cfg.activation.depth,
        "activation.x_max": cfg.activation.x_max,
        "activation.kernel": bool(cfg.activation.use_kernel and cfg.fuse_mlp),
        "num_local_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k if moe else 0,
        "shared_expert_intermediate_size":
            cfg.d_ff if moe and cfg.shared_expert else 0,
        "sliding_window": cfg.sliding_window,
        "attention_bias": cfg.qkv_bias,
        "mamba_d_state": cfg.ssm_state if mamba else 0,
        "mamba_d_conv": cfg.conv_kernel if mamba else 0,
        "mamba_expand x hidden_size": cfg.d_inner_ if mamba else 0,
        "mamba_dt_rank": cfg.dt_rank_ if mamba else 0,
        "gated_ffn": cfg.glu if cfg.has_ffn else True,
        "num_codebooks": cfg.n_codebooks,
    }
    want = dict(conf, **{f"activation.{k}": v for k, v in act.items()})
    # a file's experts: Mixtral's key, or the one Jamba and Qwen-MoE
    # use; one expert routed top-1 is a dense FFN
    experts = int(conf.get("num_local_experts", conf.get("num_experts", 0)))
    want["num_local_experts"] = experts if experts > 1 else 0
    want["num_experts_per_tok"] = (conf.get("num_experts_per_tok", 0)
                                   if experts > 1 else 0)
    want["mamba_expand x hidden_size"] = (conf.get("mamba_expand", 0)
                                          * conf["hidden_size"])
    dense = {"shared_expert_intermediate_size": 0, "sliding_window": None,
             "attention_bias": False, "mamba_d_state": 0, "mamba_d_conv": 0,
             "mamba_dt_rank": 0, "gated_ffn": True, "num_codebooks": 1}
    for k, v in dense.items():
        want.setdefault(k, v)
    bad = {k: (want.get(k), v) for k, v in have.items() if want.get(k) != v}
    file_kinds = flops.layer_kinds(conf)
    if file_kinds != kinds:
        bad["layer pattern"] = (runs(file_kinds), runs(kinds))
    for k, v in prog.get("fields", {}).items():
        got = getattr(cfg, k, "<no such attribute>")
        if got != v:
            bad[f"program.fields.{k}"] = (v, got)
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file (file, program): {bad}")
    return cfg


def program_layer_kinds(cfg) -> list[str]:
    """The kind of each layer the program runs (``full``, ``sliding``,
    ``mamba`` or ``hybrid``, as ``flops.KINDS`` names them): the
    config's own ``layer_kinds`` where a patterned program exposes it;
    else one kind for the whole stack, as the program builds it: Mamba
    mixers under ``use_mamba``, attention and Mamba side by side under
    ``parallel_mamba``, windowed attention where ``sliding_window`` is
    set, and full attention otherwise."""
    kinds = getattr(cfg, "layer_kinds", None)
    if kinds is not None:
        return list(kinds)
    if cfg.use_mamba:
        kind = "mamba"
    elif cfg.parallel_mamba:
        kind = "hybrid"
    else:
        kind = "sliding" if cfg.sliding_window else "full"
    return [kind] * cfg.n_layers


def runs(kinds: list[str]) -> str:
    """A layer pattern in runs of one kind: 'mamba*7 full mamba*6'."""
    out = []
    for kind, group in itertools.groupby(kinds):
        n = len(list(group))
        out.append(f"{kind}*{n}" if n > 1 else kind)
    return " ".join(out)


def make_params(cfg, seed: int):
    """Serving weights: the benchmark's fill in bf16 over the shapes of
    the program's init, and the program's own activation table, cast to
    bf16 as ``launch/serve.py`` serves it."""
    import jax
    import jax.numpy as jnp
    from repro.core.activations import init_act_params
    from repro.models import model as M

    from . import weights
    shapes, _ = M.abstract_params(cfg)
    act = shapes.pop("act", None)
    params = weights.fill(shapes, seed, jnp.bfloat16)
    if act is not None:
        tables = init_act_params(cfg.layer_activation_configs())
        params["act"] = {k: jnp.asarray(v, jnp.bfloat16)
                         for k, v in tables.items()}
    return jax.block_until_ready(params)


def param_shapes(cfg):
    """The parameter tree's shapes without the activation table."""
    from repro.models import model as M
    shapes, _ = M.abstract_params(cfg)
    shapes.pop("act", None)
    return shapes


def engine_config(spec: dict):
    from repro.serve import EngineConfig
    return EngineConfig(**spec)


def make_engine(cfg, params, spec: dict):
    from repro.serve import ServeEngine
    return ServeEngine(cfg, params, engine_config(spec))


def chunk_bucket(c: int, chunk_prefill: int) -> int:
    """The padded length of a prefill chunk of ``c`` tokens (the engine's
    bucket policy: powers of two from 16, capped at the chunk size)."""
    from repro.serve.scheduler import bucket_len
    return bucket_len(c, min_bucket=min(16, chunk_prefill),
                      max_len=chunk_prefill)


def warm_up(engine, vocab_size: int, seed: int) -> dict:
    """Run every program shape the cell's traffic can reach, through the
    engine's public surface: one request per decode-chunk length
    n = 1 .. chunk (a request with n + 1 new tokens decodes one chunk of
    n steps, the length drain trimming or the token budget can cut), with
    prompts that give each prefill-chunk bucket. Content is drawn from
    ``seed`` on a stream of its own, so it shares no prefix with the
    traffic. Returns the shapes covered."""
    import numpy as np
    ecfg = engine.ecfg
    cp = ecfg.chunk_prefill
    buckets = sorted({chunk_bucket(c, cp) for c in range(1, cp + 1)})
    rng = np.random.default_rng([int(seed), 1])
    n_req = max(ecfg.chunk, len(buckets))
    for i in range(n_req):
        n = i % ecfg.chunk + 1
        plen = min(buckets[i % len(buckets)], ecfg.max_prompt_len)
        engine.submit(rng.integers(0, vocab_size, size=plen, dtype=np.int32),
                      n + 1)
        engine.run()
    engine.completions.clear()
    return {"decode_lengths": list(range(1, ecfg.chunk + 1)),
            "prefill_buckets": buckets, "requests": n_req}
