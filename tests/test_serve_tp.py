"""Tensor-parallel serving equivalence on a forced multi-device host.

The mesh-aware ServeEngine must be a pure layout change: serving under
TP=2 and TP=4 emits token-for-token (greedy) what TP=1 emits. jax locks
the device count at first init, and the main pytest process has long
since initialized a 1-CPU backend — so the check runs in ONE subprocess
that sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
importing jax (the launch/dryrun.py pattern) and serves the same
workload at every TP width.
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import serve_batch
from repro.models import model as M
from repro.parallel import partition as part

assert len(jax.devices()) == 8, jax.devices()
cfg = registry.get("qwen3-0.6b", smoke=True)
params, _ = M.materialize_params(cfg, seed=0)
params = jax.tree.map(
    lambda a: a.astype(jnp.bfloat16)
    if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
rng = np.random.RandomState(0)
prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (3, 12)).astype(np.int32))

outs = {}
for tp in (1, 2, 4):
    mesh = make_host_mesh(1, tp)
    assert dict(mesh.shape)["model"] == tp, mesh.shape
    with part.axis_rules(mesh):
        tokens, _ = serve_batch(cfg, params, prompts, 8, mesh=mesh)
    outs[tp] = np.asarray(tokens)

for tp in (2, 4):
    assert np.array_equal(outs[tp], outs[1]), (
        f"TP={tp} diverged from TP=1",
        outs[tp].tolist(), outs[1].tolist())

# the paged pool (serve_batch default) and the legacy per-slot cache
# must agree under TP too: the pool's page dim is host-addressed like
# slots, so sharding is a pure layout change for both contracts
mesh = make_host_mesh(1, 2)
with part.axis_rules(mesh):
    slot_tokens, _ = serve_batch(cfg, params, prompts, 8, mesh=mesh,
                                 cache="slot")
assert np.array_equal(np.asarray(slot_tokens), outs[2]), (
    "TP=2 slot cache diverged from TP=2 paged",
    np.asarray(slot_tokens).tolist(), outs[2].tolist())

# the token-budget schedule (chunked prefill) must also be a pure
# scheduling change under TP: same workload, chunked at TP=2, equals
# the one-shot TP results
mesh = make_host_mesh(1, 2)
with part.axis_rules(mesh):
    chunked_tokens, _ = serve_batch(cfg, params, prompts, 8, mesh=mesh,
                                    chunk_prefill=4)
assert np.array_equal(np.asarray(chunked_tokens), outs[2]), (
    "TP=2 chunked prefill diverged from TP=2 one-shot",
    np.asarray(chunked_tokens).tolist(), outs[2].tolist())

# multi-codebook serving is engine-only now, so TP must cover it too:
# the K-plane embed/head tensors carry a "codebook" logical axis that
# stays replicated while vocab/heads shard — still a pure layout change
mcfg = registry.get("musicgen-large", smoke=True)
mparams, _ = M.materialize_params(mcfg, seed=0)
mparams = jax.tree.map(
    lambda a: a.astype(jnp.bfloat16)
    if jnp.issubdtype(a.dtype, jnp.floating) else a, mparams)
mprompts = jnp.asarray(rng.randint(
    0, mcfg.vocab_size, (2, 10, mcfg.n_codebooks)).astype(np.int32))
mouts = {}
for tp in (1, 2):
    mesh = make_host_mesh(1, tp)
    with part.axis_rules(mesh):
        tokens, _ = serve_batch(mcfg, mparams, mprompts, 6, mesh=mesh)
    mouts[tp] = np.asarray(tokens)
assert mouts[1].shape == (2, 6, mcfg.n_codebooks), mouts[1].shape
assert np.array_equal(mouts[2], mouts[1]), (
    "musicgen TP=2 diverged from TP=1",
    mouts[2].tolist(), mouts[1].tolist())
print("TP-IDENTITY-OK")
"""


def test_tp_serving_token_identical_to_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "TP-IDENTITY-OK" in proc.stdout


NAMES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.parallel import partition as part
from repro.serve import EngineConfig, ServeEngine

def module_name(lowered):
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split(" ", 1)[0]

cfg = registry.get("qwen3-0.6b", smoke=True)
params, _ = M.materialize_params(cfg, seed=0)
mesh = make_host_mesh(1, 2)
i32 = jnp.int32
with part.axis_rules(mesh):
    eng = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_prompt_len=16, max_len=24, page_size=4,
        chunk_prefill=8), mesh=mesh)
    dec = eng._decode_at(eng.ecfg.chunk).lower(eng.params, eng.cache,
                                               eng.state)
    pf = eng._chunk_at(8).lower(
        eng.params, eng.cache, eng.state, {"tokens": jnp.zeros((1, 8), i32)},
        i32(0), i32(0), i32(3), jnp.asarray(True), jnp.asarray(True),
        i32(0), eng._base_key, jnp.zeros((1,), jnp.float32), i32(4), i32(-1))
names = (module_name(dec), module_name(pf))
assert names == ("jit_decode_chunk", "jit_prefill_chunk"), names
print("TP-NAMES-OK")
"""


def test_tp_engine_programs_keep_their_names():
    """Under a mesh every program is traced through the engine's rule
    context; the wrapper keeps the program's name, so a TP engine's
    modules read ``jit_decode_chunk`` / ``jit_prefill_chunk`` too."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", NAMES_SCRIPT], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "TP-NAMES-OK" in proc.stdout
