"""The harness opened to every architecture the program serves: the
weight fill over every registry arch's leaves at the init's scale, the
FLOP count by layer kind, the configuration check that states experts,
windows and Mamba sizes, and the dense reference refusing what it does
not compute. The two shipped configurations keep the parent's fills and
FLOP counts exactly (pinned)."""
import dataclasses
import hashlib
import math
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

from chipbench import check, files, flops, program, weights  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.common import fused_of  # noqa: E402
from test_chipbench_run import TINY_CONF  # noqa: E402


def _smoke(arch):
    return registry.get(arch, smoke=True)


def _by_path(tree):
    import jax
    return {"/".join(str(getattr(k, "key", k)) for k in p): np.asarray(a)
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


# -- the weight fill ---------------------------------------------------------

# The fill and the init draw each random leaf independently, so their
# standard deviations agree only to sampling noise. The smallest random
# leaf of a smoke config holds 512 values (a router [2, 64, 4]); the
# standard deviation of n normal draws has a relative error of about
# 1/sqrt(2n) (3.1% at 512), the ratio of two such about 4.4%. A log-ratio
# within 0.2 is over four of those, and still refuses the smallest scale
# error a rule can make here: experts drawn at 1/sqrt(E) with E = 4 where
# the init uses 1/sqrt(d) with d = 64 (a factor 4), or a fan-in over
# d_conv = 4 where it is d_inner = 128.
STD_LOG_TOL = 0.2


@pytest.mark.parametrize("arch", registry.ARCHS)
def test_fill_matches_the_init_leaf_by_leaf(arch):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    cfg = _smoke(arch)
    init, _ = M.materialize_params(cfg)
    init.pop("act", None)
    init = _by_path(init)
    got = _by_path(weights.fill(program.param_shapes(cfg), 2**31 + 9,
                                jnp.float32))
    assert set(got) == set(init)
    for path, a in got.items():
        b = init[path]
        assert a.shape == b.shape, path
        kind = weights.rule_of(path.split("/"))[0]
        if kind in ("ones", "zeros", "s4d_real"):
            assert np.array_equal(a, b), path
        elif kind == "dt_bias":
            for x in (a, b):
                dt = np.asarray(jax.nn.softplus(jnp.asarray(x)))
                assert dt.min() >= 1e-3 * (1 - 1e-5), path
                assert dt.max() <= 1e-1 * (1 + 1e-5), path
            assert len(np.unique(a)) == a.size, path
        else:
            assert abs(math.log(a.std() / b.std())) < STD_LOG_TOL, \
                (path, a.std(), b.std())


def test_moe_experts_draw_at_fan_in_of_their_own_weight():
    """[L, E, d, f] experts at 1/sqrt(d), [L, E, f, d] at 1/sqrt(f): the
    expert axis is a stack, like the layer axis."""
    import jax.numpy as jnp
    cfg = registry.get("mixtral-8x22b", smoke=True, d_ff=1024)
    got = _by_path(weights.fill(program.param_shapes(cfg), 3, jnp.float32))
    for name, fan in (("w_gate", 64), ("w_up", 64), ("w_down", 1024),
                      ("router", 64)):
        std = got[f"blocks/ffn/{name}"].std()
        assert std == pytest.approx(1 / math.sqrt(fan), rel=0.02), name


def test_every_leaf_name_has_one_rule():
    with pytest.raises(KeyError, match="no weight rule for leaf blocks/x/odd"):
        weights.rule_of(["blocks", "x", "odd"])


def _fill_digest(arch, fused, seed):
    import jax
    cfg = _smoke(arch)
    if fused:
        cfg = fused_of(cfg)
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(
            weights.fill(program.param_shapes(cfg), seed))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).view(np.uint16).tobytes())
    return h.hexdigest()


# sha256 over (path, bf16 bits) of every leaf, as the fill gave them before
# it learned experts and Mamba leaves
PINNED_FILLS = [
    ("qwen3-0.6b", False, 0,
     "7b06087457c88d38d3c9c044d97327f7de3066ab8b9045a5daf711b6dac80e56"),
    ("qwen3-0.6b", False, 2**31 + 5,
     "80e7b5ebfc4be0b28ec84da03f36cf007a9a3ad2dee4c99de8128d0020e16cda"),
    ("olmo-1b", True, 0,
     "ad5983d8a261db6bfe0171a001dead7122425c6b15a0c2b56aad24a1348d83da"),
    ("olmo-1b", True, 2**31 + 5,
     "fb20f83d38796d9cc865e010b2282ec2812ef8585810e919c75e281526150520"),
]


@pytest.mark.parametrize("arch,fused,seed,digest", PINNED_FILLS,
                         ids=[f"{a}-{s}" for a, _, s, _ in PINNED_FILLS])
def test_dense_fills_are_bitwise_as_before(arch, fused, seed, digest):
    assert _fill_digest(arch, fused, seed) == digest


# -- FLOPs by layer kind -----------------------------------------------------

# (start, n) -> span_flops, and head_flops, as the count gave them before
# it learned layer kinds: the mfu.* readings rest on these
PINNED_FLOPS = {
    "qwen3-0.6b": (311164928.0, {
        (0, 1): 881033216.0, (0, 512): 481095057408.0,
        (511, 1): 998244352.0, (1024, 512): 601354141696.0,
        (2047, 7): 9458778112.0, (7, 9): 7952007168.0}),
    "olmo-1b-fused": (206045184.0, {
        (0, 1): 2147614720.0, (0, 512): 1116725051392.0,
        (511, 1): 2214592512.0, (1024, 512): 1185444528128.0,
        (2047, 7): 16914186240.0, (7, 9): 19341508608.0}),
}


@pytest.mark.parametrize("name", sorted(PINNED_FLOPS))
def test_shipped_flops_are_exactly_as_before(name):
    c = files.config_of(name)
    head, spans = PINNED_FLOPS[name]
    assert flops.head_flops(c) == head
    for (start, n), want in spans.items():
        assert flops.span_flops(c, start, n) == want
    assert flops.layer_kinds(c) == ["full"] * c["num_hidden_layers"]


TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32}


def test_flops_of_a_jamba_shaped_config_by_hand():
    c = dict(TINY, num_hidden_layers=4, attn_layer_period=2,
             attn_layer_offset=1, mamba_expand=2, mamba_d_state=4,
             mamba_dt_rank=2, mamba_d_conv=3, num_experts=1,
             num_experts_per_tok=1)
    assert flops.layer_kinds(c) == ["mamba", "full", "mamba", "full"]
    # attention layer: q, k, v, o = 64 + 32 + 32 + 64 weights, FFN 3 x 8 x 16
    # = 384: 2 x 576 a token, and 4 x 2 heads x 4 x (1 + 2 + 3) keys
    attn = 2 * 576 * 3 + 32 * 6
    # Mamba layer: in_proj 8 x 32, x_proj 16 x (2 + 8), dt_proj 2 x 16,
    # out_proj 16 x 8 = 576, FFN 384: 2 x 960 a token; conv 2 x 3 x 16,
    # scan 6 x 16 x 4
    mamba = (2 * 960 + 96 + 384) * 3
    assert flops.span_flops(c, 0, 3) == 2 * attn + 2 * mamba == 21696


def test_flops_of_a_mixtral_shaped_config_by_hand():
    c = dict(TINY, num_hidden_layers=2, num_local_experts=4,
             num_experts_per_tok=2, sliding_window=4)
    assert flops.layer_kinds(c) == ["sliding", "sliding"]
    # attention 192 weights, two experts of 384 and a router of 8 x 4:
    # 2 x 992 a token; positions 2 .. 6 see 3, 4, 4, 4, 4 keys
    per_layer = 2 * 992 * 5 + 32 * (3 + 4 + 4 + 4 + 4)
    assert flops.span_flops(c, 2, 5) == 2 * per_layer == 21056
    shared = dict(c, shared_expert_intermediate_size=16)
    assert flops.span_flops(shared, 2, 5) == 21056 + 2 * 2 * 384 * 5
    # an FFN without a gate multiplies through two matrices, not three;
    # each codebook has a head of its own
    plain = dict(c, gated_ffn=False)
    assert flops.span_flops(plain, 2, 5) == 21056 - 2 * 2 * 2 * 128 * 5
    assert flops.head_flops(dict(c, num_codebooks=4)) == 4 * 2 * 8 * 32
    # inside the window the cap changes nothing
    assert flops.span_flops(c, 0, 4) == \
        flops.span_flops(dict(c, sliding_window=None), 0, 4)


def test_layer_kinds_from_the_file():
    c = dict(TINY, num_hidden_layers=3,
             layer_types=["full_attention", "sliding_attention", "hybrid"])
    assert flops.layer_kinds(c) == ["full", "sliding", "hybrid"]
    assert flops.layer_kinds(dict(TINY, num_hidden_layers=2,
                                  num_attention_heads=0)) == ["mamba"] * 2
    with pytest.raises(ValueError, match="layer_types holds 3"):
        flops.layer_kinds(dict(c, num_hidden_layers=4))
    with pytest.raises(KeyError):
        flops.layer_kinds(dict(c, layer_types=["linear_attention"] * 3))


@pytest.mark.parametrize("start,n", [(0, 9), (5, 40), (60, 3)])
def test_span_flops_is_the_sum_of_token_flops_for_every_kind(start, n):
    c = dict(TINY, num_hidden_layers=3, sliding_window=16, mamba_expand=2,
             mamba_d_state=4, mamba_dt_rank=2, mamba_d_conv=3,
             num_local_experts=4, num_experts_per_tok=2,
             layer_types=["full_attention", "sliding_attention", "hybrid"])
    want = sum(flops.token_flops(c, p + 1) for p in range(start, start + n))
    assert flops.span_flops(c, start, n) == pytest.approx(want)


# -- the configuration check -------------------------------------------------

def _file(arch, **keys):
    """A configuration file for a registry arch's smoke config that
    states its sizes and mechanisms (or others, through ``keys``)."""
    cfg = _smoke(arch)
    conf = {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim_,
        "vocab_size": cfg.vocab_size,
        "norm": cfg.norm, "qk_norm": cfg.qk_norm, "hidden_act": cfg.mlp_act,
        "compute_dtype": cfg.compute_dtype,
        "activation": {"impl": "cr", "depth": 32, "x_max": 4.0,
                       "kernel": False},
        "program": {"arch": arch, "smoke": True}}
    if cfg.rope_kind != "none":
        conf["rope_theta"] = cfg.rope_theta
    if cfg.parallel_mamba:
        conf["layer_types"] = ["hybrid"] * cfg.n_layers
    if cfg.n_experts:
        conf.update(num_local_experts=cfg.n_experts,
                    num_experts_per_tok=cfg.top_k)
    if cfg.shared_expert:
        conf["shared_expert_intermediate_size"] = cfg.d_ff
    if cfg.sliding_window:
        conf["sliding_window"] = cfg.sliding_window
    if cfg.use_mamba or cfg.parallel_mamba:
        conf.update(mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.conv_kernel,
                    mamba_expand=cfg.d_inner_ // cfg.d_model,
                    mamba_dt_rank=cfg.dt_rank_)
    if not cfg.glu:
        conf["gated_ffn"] = False
    if cfg.n_codebooks > 1:
        conf["num_codebooks"] = cfg.n_codebooks
    conf.update(keys)
    return conf


ARCH_FILES = ["mixtral-8x22b", "llama4-scout-17b-a16e", "falcon-mamba-7b",
              "hymba-1.5b", "qwen2.5-3b", "musicgen-large"]


@pytest.mark.parametrize("arch", ARCH_FILES)
def test_model_config_accepts_a_file_that_states_the_mechanisms(arch):
    conf = _file(arch)
    if arch == "qwen2.5-3b":
        conf["attention_bias"] = True
    assert program.model_config(conf).name == _smoke(arch).name


LEFT_OUT = [("mixtral-8x22b", "num_local_experts"),
            ("mixtral-8x22b", "sliding_window"),
            ("llama4-scout-17b-a16e", "shared_expert_intermediate_size"),
            ("hymba-1.5b", "sliding_window"),
            ("hymba-1.5b", "mamba_d_state"),
            ("falcon-mamba-7b", "mamba_expand"),
            ("qwen2.5-3b", "attention_bias"),
            ("musicgen-large", "gated_ffn"),
            ("musicgen-large", "num_codebooks")]


@pytest.mark.parametrize("arch,key", LEFT_OUT,
                         ids=[f"{a}-{k}" for a, k in LEFT_OUT])
def test_model_config_refuses_a_file_that_leaves_a_mechanism_out(arch, key):
    conf = _file(arch)
    conf.pop(key, None)
    with pytest.raises(ValueError, match="differs from the configuration"):
        program.model_config(conf)


def test_model_config_refuses_a_mechanism_the_program_lacks():
    with pytest.raises(ValueError, match="sliding_window"):
        program.model_config(_file("qwen3-0.6b", sliding_window=4096))
    with pytest.raises(ValueError, match="num_local_experts"):
        program.model_config(dict(TINY_CONF, num_local_experts=8,
                                  num_experts_per_tok=2))


def test_a_ropeless_file_leaves_rope_theta_out():
    """Falcon-Mamba's file, like Jamba's, has no rope_theta: it applies
    no rotation. The check compares the key only where the program
    rotates."""
    conf = _file("falcon-mamba-7b")
    assert "rope_theta" not in conf
    assert program.model_config(conf).rope_kind == "none"
    with pytest.raises(ValueError, match="rope_theta"):
        program.model_config(dict(conf, rope_theta=10000.0))
    with pytest.raises(ValueError, match="rope_theta"):
        program.model_config({k: v for k, v in TINY_CONF.items()
                              if k != "rope_theta"})


def test_model_config_refuses_a_layer_pattern_the_program_does_not_run(
        monkeypatch):
    """A file that states Jamba's pattern (attention at layers 7, 21, ...
    of period 14, Mamba elsewhere) under a program whose layers are all
    Mamba: the FLOP count would follow the file, so the check refuses
    it."""
    cfg = _smoke("falcon-mamba-7b")
    cfg = dataclasses.replace(cfg, n_layers=8, name="mamba-8")
    monkeypatch.setitem(registry._DYNAMIC, "mamba-8", (cfg, cfg))
    conf = _file("falcon-mamba-7b", num_hidden_layers=8,
                 program={"arch": "mamba-8", "smoke": True})
    assert program.program_layer_kinds(cfg) == ["mamba"] * 8
    assert program.model_config(conf).n_layers == 8
    jamba = dict(conf, attn_layer_period=14, attn_layer_offset=7)
    assert flops.layer_kinds(jamba)[7] == "full"
    with pytest.raises(ValueError, match="layer pattern.*"
                       r"'mamba\*7 full', 'mamba\*8'"):
        program.model_config(jamba)


def test_a_patterned_program_states_its_layer_kinds(monkeypatch):
    """A program config that exposes ``layer_kinds`` is held to the file
    by it, layer for layer."""
    cfg = _smoke("falcon-mamba-7b")
    pattern = ("mamba", "full", "mamba", "full")

    @dataclasses.dataclass(frozen=True)
    class Patterned(type(cfg)):
        layer_kinds: tuple = pattern

    p = Patterned(**dict(
        {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)},
        n_layers=4, name="patterned"))
    monkeypatch.setitem(registry._DYNAMIC, "patterned", (p, p))
    conf = _file("falcon-mamba-7b", num_hidden_layers=4,
                 attn_layer_period=2, attn_layer_offset=1,
                 program={"arch": "patterned", "smoke": True})
    assert program.program_layer_kinds(p) == list(pattern)
    assert program.model_config(conf).name == "patterned"
    with pytest.raises(ValueError, match="layer pattern"):
        program.model_config(dict(conf, attn_layer_offset=0))


def test_one_expert_routed_top_1_is_a_dense_ffn():
    """Jamba's file states num_experts 1 and num_experts_per_tok 1 for
    its dense FFNs; the check reads that as no experts."""
    conf = dict(TINY_CONF, num_experts=1, num_experts_per_tok=1)
    assert program.model_config(conf).n_experts == 0


def test_program_fields_are_held_by_getattr():
    conf = _file("falcon-mamba-7b")
    conf["program"]["fields"] = {"use_mamba": True, "family": "ssm"}
    assert program.model_config(conf).use_mamba
    conf["program"]["fields"] = {"use_mamba": False}
    with pytest.raises(ValueError, match="program.fields.use_mamba"):
        program.model_config(conf)
    conf["program"]["fields"] = {"layer_pattern": "AMMM"}
    with pytest.raises(ValueError, match="no such attribute"):
        program.model_config(conf)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "olmo-1b-fused"])
def test_shipped_files_pass_unedited(name):
    conf = files.config_of(name)
    cfg = program.model_config(conf)
    check.reference_module(conf).check_shapes(program.param_shapes(cfg), conf)


# -- the dense reference guards itself ---------------------------------------

def _dense_ref():
    return check.reference_module({"reference": "dense"})


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "falcon-mamba-7b",
                                  "hymba-1.5b", "qwen2.5-3b"])
def test_dense_reference_refuses_a_tree_it_does_not_compute(arch):
    """A non-dense tree under a file of dense sizes: its extra leaves
    (router, experts, Mamba, biases) are refused."""
    cfg = _smoke(arch)
    conf = dict(TINY_CONF, num_attention_heads=cfg.n_heads or 4,
                num_key_value_heads=cfg.n_kv_heads or 2, qk_norm=False)
    with pytest.raises(ValueError, match="computes no leaf|shape"):
        _dense_ref().check_shapes(program.param_shapes(cfg), conf)


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("num_experts", 16), ("sliding_window", 4096),
    ("mamba_d_state", 16), ("mamba_expand", 2), ("attention_bias", True),
    ("layer_types", ["full_attention"] * 2), ("attn_layer_period", 8),
    ("gated_ffn", False), ("num_codebooks", 4)])
def test_dense_reference_refuses_a_file_that_states_a_mechanism(key, value):
    ref = _dense_ref()
    shapes = program.param_shapes(program.model_config(TINY_CONF))
    ref.check_shapes(shapes, TINY_CONF)
    with pytest.raises(ValueError, match="does not compute"):
        ref.check_shapes(shapes, dict(TINY_CONF, **{key: value}))


def test_dense_reference_still_checks_shapes():
    ref = _dense_ref()
    shapes = program.param_shapes(program.model_config(TINY_CONF))
    with pytest.raises(ValueError, match="blocks/ffn/w_gate"):
        ref.check_shapes(shapes, dict(TINY_CONF, intermediate_size=256))
    shapes["blocks"]["attn"].pop("q_norm")
    with pytest.raises(ValueError, match="lacks"):
        ref.check_shapes(shapes, TINY_CONF)

