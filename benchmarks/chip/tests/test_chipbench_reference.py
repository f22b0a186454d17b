"""The check's references take the fill as the program serves it
(bfloat16) and widen one layer at a time: their gaps are bit for bit
those of the same fill widened whole before the call, with the float8
control and without; ``judge`` hands them the bfloat16 leaves; and the
compiled dense reference holds about one layer in float32, never the
whole tree."""
import math
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

from chipbench import bench, check, program, weights  # noqa: E402
from test_chipbench_moe_run import MOE_CONF  # noqa: E402
from test_chipbench_run import TINY_CONF, TINY_MIX  # noqa: E402


def _reference(conf, tmp_path):
    """The reference a configuration names; the Mixtral one lives with
    the tests, and is read from a root of its own beside ``dense.py``."""
    if conf["reference"] == "dense":
        return check.reference_module(conf)
    (tmp_path / "reference").mkdir()
    shutil.copy(CHIP / "reference" / "dense.py", tmp_path / "reference")
    shutil.copy(CHIP / "tests" / "moe_window_reference.py",
                tmp_path / "reference" / "moe_window.py")
    return check.reference_module(conf, tmp_path)


def _requests(conf, seed, n=3):
    """Prompts with served tokens drawn at random: gaps well above 0, so
    a changed bit anywhere shows."""
    rng = np.random.default_rng(seed)
    out = []
    for uid in range(n):
        prompt = rng.integers(0, conf["vocab_size"], int(rng.integers(8, 40)))
        out.append(types.SimpleNamespace(
            uid=uid, prompt=prompt.astype(np.int32), prompt_len=len(prompt),
            tokens=[int(t) for t in rng.integers(0, conf["vocab_size"], 12)]))
    return out


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
@pytest.mark.parametrize("conf", [TINY_CONF, MOE_CONF],
                         ids=["dense", "moe_window"])
def test_gaps_from_bf16_fill_are_those_of_the_fill_widened_whole(
        conf, control, tmp_path):
    import jax
    import jax.numpy as jnp
    ref = _reference(conf, tmp_path)
    seed = 2**31 + 7
    w = weights.fill(program.param_shapes(program.model_config(conf)), seed)
    assert {a.dtype for a in jax.tree.leaves(w)} == {jnp.dtype(jnp.bfloat16)}
    wide = jax.tree.map(lambda a: a.astype(np.float32), w)
    ref.check_shapes(w, conf)
    reqs = _requests(conf, seed)
    narrow_rows = check.gaps(ref, w, conf, reqs, control=control, max_seq=64)
    wide_rows = check.gaps(ref, wide, conf, reqs, control=control, max_seq=64)
    assert narrow_rows == wide_rows
    assert all(r["gap"] > 0.01 for r in narrow_rows)
    if control:
        assert all(math.isfinite(r["control_gap"]) for r in narrow_rows)


def test_judge_hands_the_reference_the_bf16_fill(monkeypatch):
    import jax
    import jax.numpy as jnp
    seen = []
    gaps = check.gaps

    def spy(ref, w, *a, **k):
        seen.extend(a.dtype for a in jax.tree.leaves(w))
        return gaps(ref, w, *a, **k)

    monkeypatch.setattr(check, "gaps", spy)
    ok, numbers, rows = bench.judge(TINY_CONF, TINY_MIX,
                                    _requests(TINY_CONF, 3), 3, CHIP)
    assert seen and set(seen) == {jnp.dtype(jnp.bfloat16)}
    assert len(rows) == 3 and math.isfinite(numbers["max_logit_gap"]["value"])


def _stacked_tree(L, d, f, H, KV, hd, V, sharding=None):
    """ShapeDtypeStructs of a dense tree of ``L`` layers in bfloat16, and
    its configuration."""
    import jax
    import jax.numpy as jnp
    conf = dict(TINY_CONF, hidden_size=d, intermediate_size=f,
                num_hidden_layers=L, num_attention_heads=H,
                num_key_value_heads=KV, head_dim=hd, vocab_size=V)
    blocks = {"attn": {"wq": (L, d, H, hd), "wk": (L, d, KV, hd),
                       "wv": (L, d, KV, hd), "wo": (L, H, hd, d),
                       "q_norm": (L, hd), "k_norm": (L, hd)},
              "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                      "w_down": (L, f, d)},
              "ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)}}
    w = {"blocks": blocks, "embed": (V, d), "lm_head": (d, V),
         "ln_f": {"scale": (d,)}}
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding),
        w, is_leaf=lambda x: isinstance(x, tuple))
    return w, conf


def test_dense_reference_widens_inside_the_layer_scan():
    """As JAX lowers it, every widening of a stacked [L, ...] leaf to
    float32 happens on its layer's slice inside the scan: nothing widens
    a whole stack."""
    import re

    import jax
    import jax.numpy as jnp
    w, conf = _stacked_tree(8, 64, 128, 4, 2, 16, 512)
    ref = check.reference_module(conf)
    text = ref.hidden.lower(w, jax.ShapeDtypeStruct((32,), jnp.int32),
                            conf_key=ref.conf_key(conf)).as_text()
    converts = re.findall(r"stablehlo.convert.*: \((tensor<[^>]*>)\)", text)
    widened = [t for t in converts if "bf16" in t]
    assert widened, "the reference widens nothing"
    assert not [t for t in widened if t.startswith("tensor<8x")], widened


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a described TPU v5e, with JAX's persistent cache off
    (a compile for it is written there and cannot be read back)."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
def test_dense_reference_holds_one_layer_in_float32_not_the_tree(one_v5e,
                                                                 control):
    """The reference compiled for a v5e over a bfloat16 tree of 8 layers:
    its temporaries stay under half the bytes of that tree in float32
    (0.15 GB against 0.49 GB). A reference that widened the whole tree,
    or a compiler that hoisted the widening out of the layer scan, holds
    all of it (0.64 GB). The CPU's compiler widens every bfloat16 loop
    operand itself, so only the chip's compiler can show this."""
    import jax
    import jax.numpy as jnp
    w, conf = _stacked_tree(8, 1024, 4096, 8, 2, 128, 512, one_v5e)
    ref = check.reference_module(conf)
    ref.check_shapes(w, conf)
    wide_bytes = 4 * sum(math.prod(a.shape) for a in jax.tree.leaves(w))
    tokens = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_v5e)
    ma = ref.hidden.lower(w, tokens, conf_key=ref.conf_key(conf),
                          control=control).compile().memory_analysis()
    assert ma.temp_size_in_bytes < wide_bytes / 2, \
        (ma.temp_size_in_bytes, wide_bytes)


def test_spline_in_blocks_of_rows_is_the_spline_over_all_rows():
    """Above ``SPLINE_BLOCK`` elements the reference's spline runs a block
    of rows at a time; every element comes out as from one call."""
    import jax
    import jax.numpy as jnp
    ref = check.reference_module(TINY_CONF)
    windows = ref.spline_windows(4.0, 32)
    n = 1024
    rows = ref.SPLINE_BLOCK // n
    v = 5.0 * jax.random.normal(jax.random.key(0), (2 * rows + 37, n),
                                jnp.float32)
    blocked = np.asarray(jax.jit(
        lambda v: ref.tanh_cr(v, windows, 4.0, 32))(v))
    whole = np.asarray(jax.jit(
        lambda v: ref._tanh_cr(v, windows, 4.0, 32))(v))
    assert np.array_equal(blocked.view(np.uint32), whole.view(np.uint32))
    assert np.abs(blocked - np.tanh(np.asarray(v))).max() < 1e-3
