"""The command end to end on the CPU: it refuses to run without a TPU,
and, with the look for a chip skipped, a tiny run reads ``correct``
true, and false with the timed path broken underneath it."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

from chipbench import bench  # noqa: E402

TINY_CONF = {
    "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 1000000.0, "hidden_act": "silu", "norm": "rmsnorm",
    "qk_norm": True, "compute_dtype": "bfloat16",
    "activation": {"impl": "cr", "depth": 32, "x_max": 4.0, "kernel": False},
    "program": {"arch": "qwen3-0.6b", "smoke": True, "fused": False},
    "reference": "dense",
    # clean tiny runs read 0 (every served token is the reference's
    # best); a wrong token reads about one logit unit or more
    "check": {"max_logit_gap": 0.1}}
TINY_MIX = {
    "kind": "open_loop", "rate_per_s": 40.0,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
               "min": 8, "max": 64},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
               "min": 4, "max": 16},
    "cycle": 20, "sizes_seed": 0, "warmup_s": 0.5, "drain_cap_s": 30,
    "check": {"requests": 8},
    "engine": {"slots": 4, "max_prompt_len": 64, "max_len": 96, "chunk": 4,
               "chunk_prefill": 32, "page_size": 16, "n_pages": 40}}
BENCH = {
    "workloads": [{"name": "tiny.chat", "config": "tiny", "traffic": "tiny",
                   "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": []}


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("chipbench")
    for d in ("configs", "traffic"):
        (r / d).mkdir()
    (r / "configs" / "tiny.json").write_text(json.dumps(TINY_CONF))
    (r / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    for d in ("metrics", "reference"):
        (r / d).symlink_to(CHIP / d)
    return r


def _run(root, capsys, hook=None, seed=2**31 + 5):
    rc = bench.main(["--workload", "tiny.chat", "--seed", str(seed),
                     "--seconds", "1.5", "--trace", "0"], root=root,
                    require_tpu=False, bench=BENCH, program_hook=hook,
                    compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def _wrap_decode(engine, change):
    """Wrap every decode-chunk program the engine builds with ``change``."""
    orig = engine._decode_at

    def decode_at(n):
        fn = orig(n)
        return lambda p, c, s: change(fn, p, c, s)

    engine._decode_at = decode_at


def _alter_token(engine):
    """A token altered where it is produced: the harvested stream gets
    each decoded token plus one."""
    orig = engine._harvest
    V = engine.cfg.vocab_size

    def harvest(active, toks, now):
        return orig(active, (np.asarray(toks) + 1) % V, now)

    engine._harvest = harvest


def _stale_state(engine):
    """A step that returns its state unchanged: the decode chunk's new
    cache (keys, values, positions) is dropped."""
    import jax
    import jax.numpy as jnp

    def change(fn, p, c, s):
        _, state, toks = fn(p, jax.tree.map(jnp.copy, c), s)
        return c, state, toks
    _wrap_decode(engine, change)


def _half_batch(engine):
    """Half of the batch left out: odd slots' decoded tokens never come
    from the model (they read 0)."""
    def change(fn, p, c, s):
        cache, state, toks = fn(p, c, s)
        return cache, state, toks.at[:, 1::2].set(0)
    _wrap_decode(engine, change)


def test_tiny_run_is_correct(root, capsys):
    res = _run(root, capsys)
    assert res["correct"] is True
    assert res["check"]["max_logit_gap"]["value"] <= 0.1
    assert set(res["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] > 10
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("fault", [_alter_token, _stale_state, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_broken_timed_path_reads_incorrect(root, capsys, fault):
    res = _run(root, capsys, hook=fault)
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > 0.1


def test_glu_calls_match_the_engine_counters(root, capsys, monkeypatch):
    """The fused-GLU calls the harness attributes to each step (rows M,
    one per layer per decode step and per prefill chunk) add up to what
    the engine's own counters say it dispatched: the count that
    ``readers.glu_roofline`` holds against the trace's events."""
    drivers, engines = [], []

    class Capture(bench.Driver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            drivers.append(self)

    def hook(engine):
        engines.append((engine, engine.snapshot()))

    monkeypatch.setattr(bench, "Driver", Capture)
    assert _run(root, capsys, hook=hook)["correct"] is True
    [(engine, before)], [driver] = engines, drivers
    after = engine.stats
    L = TINY_CONF["num_hidden_layers"]
    steps = after.decode_steps - before.decode_steps
    chunks = after.prefill_chunks - before.prefill_chunks
    padded = after.prefill_padded_tokens - before.prefill_padded_tokens
    rows = [e for s in driver.steps for e in s.glu_rows]
    assert chunks > 0 and steps > 0
    assert sum(n for _, n in rows) == L * (steps + chunks)
    assert sum(m * n for m, n in rows) == L * (
        TINY_MIX["engine"]["slots"] * steps + padded)
