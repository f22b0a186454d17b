"""The correctness control at a size a test run holds: the float32
reference decoding greedily reads a gap of 0 against itself, and the
same reference computed in float8 (the precision below the configuration's
bfloat16) reads gaps above the tiny configuration's limit, so it comes
out as not correct through the benchmark's own comparison
(``bench.judge`` with ``control=True``)."""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import bench, check, program, weights  # noqa: E402
from test_chipbench_run import TINY_CONF, TINY_MIX  # noqa: E402


def _greedy(ref, w, conf, prompt, n, width=128):
    import jax.numpy as jnp
    seq = list(prompt)
    for _ in range(n):
        pad = np.zeros(width, np.int32)
        pad[:len(seq)] = seq
        h = ref.hidden(w, jnp.asarray(pad), conf_key=ref.conf_key(conf))
        lg = np.asarray(ref.logits_at(w, h, jnp.asarray([len(seq) - 1])))
        seq.append(int(lg[0].argmax()))
    return seq[len(prompt):]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float8_control_fails_the_limit(seed):
    import jax
    conf = json.loads(json.dumps(TINY_CONF))
    cfg = program.model_config(conf)
    ref = check.reference_module(conf)
    w = weights.fill(program.param_shapes(cfg), seed)
    w = jax.tree.map(lambda a: a.astype(np.float32), w)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(6):
        prompt = rng.integers(0, conf["vocab_size"], int(rng.integers(8, 48)))
        reqs.append(types.SimpleNamespace(
            uid=i, prompt=prompt.astype(np.int32), prompt_len=len(prompt),
            tokens=_greedy(ref, w, conf, prompt, 24)))
    mix = dict(TINY_MIX, check={"requests": len(reqs)},
               engine=dict(TINY_MIX["engine"], max_len=128))
    ok, numbers, rows = bench.judge(conf, mix, reqs, seed, CHIP,
                                    control=False)
    assert ok and numbers["max_logit_gap"]["value"] == 0.0
    ok, numbers, rows = bench.judge(conf, mix, reqs, seed, CHIP, control=True)
    limit = conf["check"]["max_logit_gap"]
    assert max(r["gap"] for r in rows) == 0.0
    assert numbers["max_logit_gap"]["value"] > limit
    assert not ok
