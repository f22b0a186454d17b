"""A configuration the harness did not know, added with files alone: the
program's mixtral-8x22b smoke config (four experts routed top-2 through
the dropless ragged dispatch, a sliding window of 32 keys) served paged
and chunked through ``bench.main`` on the CPU, from a configuration,
a traffic mix and a reference (``moe_window_reference.py``) written into
a root of its own. It reads ``correct`` true, and false with the timed
path broken underneath."""
import json
import shutil
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "tests"))

from chipbench import bench  # noqa: E402
from test_chipbench_run import (BENCH, TINY_MIX, _alter_token,  # noqa: E402
                                _half_batch, _stale_state)

MOE_CONF = {
    "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 1000000.0, "hidden_act": "silu", "norm": "rmsnorm",
    "qk_norm": False, "compute_dtype": "bfloat16",
    "num_local_experts": 4, "num_experts_per_tok": 2, "sliding_window": 32,
    "attention_bias": False,
    "activation": {"impl": "cr", "depth": 32, "x_max": 4.0, "kernel": False},
    "program": {"arch": "mixtral-8x22b", "smoke": True, "fused": False},
    "reference": "moe_window",
    # clean runs read 0 to 6e-4 (seeds 7, 11, 2**31 + 5: a near-tie
    # under bf16); a wrong token, a stale cache or half the batch left
    # out read 3.4 and more, and so does the reference without its
    # window (4.1-4.6) or routed top-1 (2.0-4.0)
    "check": {"max_logit_gap": 0.1}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("chipbench_moe")
    for d in ("configs", "traffic", "reference"):
        (r / d).mkdir()
    (r / "metrics").symlink_to(CHIP / "metrics")
    (r / "configs" / "tiny.json").write_text(json.dumps(MOE_CONF))
    (r / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    shutil.copy(CHIP / "reference" / "dense.py", r / "reference")
    shutil.copy(CHIP / "tests" / "moe_window_reference.py",
                r / "reference" / "moe_window.py")
    return r


def _run(root, capsys, hook=None):
    rc = bench.main(["--workload", "tiny.chat", "--seed", str(2**31 + 5),
                     "--seconds", "1.5", "--trace", "0"], root=root,
                    require_tpu=False, bench=BENCH, program_hook=hook,
                    compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_moe_window_config_from_files_is_correct(root, capsys):
    engines = []
    res = _run(root, capsys, hook=engines.append)
    [engine] = engines
    assert engine.cfg.n_experts == 4 and engine.cfg.sliding_window == 32
    assert engine.cfg.moe_impl == "ragged"
    assert engine.paged and engine.chunked
    assert res["correct"] is True
    assert res["check"]["max_logit_gap"]["value"] <= 0.1
    assert res["attempted"] > 10


@pytest.mark.parametrize("fault", [_alter_token, _stale_state, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_moe_window_broken_timed_path_reads_incorrect(root, capsys, fault):
    res = _run(root, capsys, hook=fault)
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > 0.1


def test_moe_reference_refuses_a_dense_tree(root):
    from chipbench import check, program
    from test_chipbench_run import TINY_CONF
    ref = check.reference_module(MOE_CONF, root)
    ref.check_shapes(program.param_shapes(program.model_config(MOE_CONF)),
                     MOE_CONF)
    dense = program.param_shapes(program.model_config(TINY_CONF))
    with pytest.raises(ValueError, match="MoE reference"):
        ref.check_shapes(dense, MOE_CONF)
