#!/usr/bin/env python3
"""Compile each cell's largest dispatches for a described TPU v5e, from
a machine without one, and print ``memory_analysis()``:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--cells a,b]

For each cell: the decode chunk at the full chunk length over every slot
and the prefill chunk at the largest bucket, both against the cell's
whole page pool, with the weights in bf16. Also ``glu_2d`` alone at
olmo-1b widths (K 2048, N 8192) for the decode rows (M = slots) and the
prefill rows (M = chunk). Nothing runs; a refused compile raises here.
Run it at full depth only where the host has the memory for the
compiler (tens of GB for a 28-layer step); ``--layers`` cuts the depth.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import files  # noqa: E402


def shaped(tree, sharding, dtype_of=None):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype_of(s.dtype) if dtype_of else s.dtype,
            sharding=sharding), tree)


def report(name, compiled, t):
    ma = compiled.memory_analysis()
    gb = lambda b: f"{b / 1e9:.3f}"
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"[rehearse] {name}: compile_s={t:.1f} args_gb={gb(ma.argument_size_in_bytes)} "
          f"out_gb={gb(ma.output_size_in_bytes)} temp_gb={gb(ma.temp_size_in_bytes)} "
          f"alias_gb={gb(ma.alias_size_in_bytes)} "
          f"code_mb={ma.generated_code_size_in_bytes / 1e6:.2f} "
          f"pallas_kernel={kernel}", flush=True)


def rehearse_cell(cell, one, layers):
    import jax
    import jax.numpy as jnp
    from chipbench import program
    from repro.models import model as M
    from repro.serve import engine as E

    conf = files.config_of(cell["config"])
    mix = files.traffic_of(cell["traffic"])
    cfg = program.model_config(conf)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    eng = mix["engine"]
    B, ps, n_pages = eng["slots"], eng["page_size"], eng["n_pages"]
    bf16 = lambda d: jnp.bfloat16 if jnp.issubdtype(d, jnp.floating) else d
    shapes, _ = M.abstract_params(cfg)
    params = shaped(shapes, one, bf16)
    cache = shaped(M.paged_cache_spec(cfg, B, n_pages, ps, eng["max_len"]), one)
    sds = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one)
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = {"tok": sds((B,), jnp.int32), "key": sds(key.shape, key.dtype),
             "uid": sds((B,), jnp.int32), "emitted": sds((B,), jnp.int32),
             "active": sds((B,), jnp.bool_), "budget": sds((B,), jnp.int32),
             "temp": sds((B,), jnp.float32), "eos": sds((B,), jnp.int32)}
    tag = f"{cell['name']} (layers={cfg.n_layers})"

    t = time.perf_counter()
    dec = jax.jit(E.make_decode_chunk(cfg, eng["chunk"], paged=True),
                  donate_argnums=(1, 2))
    c = dec.lower(params, cache, state).compile()
    report(f"{tag} decode chunk x{eng['chunk']} B={B}", c, time.perf_counter() - t)

    S = eng["chunk_prefill"]
    i32 = sds((), jnp.int32)
    t = time.perf_counter()
    pf = jax.jit(E.make_chunk_prefill(cfg, ps), donate_argnums=(1, 2))
    c = pf.lower(params, cache, state, {"tokens": sds((1, S), jnp.int32)},
                 i32, i32, i32, sds((), jnp.bool_), sds((), jnp.bool_), i32,
                 sds(key.shape, key.dtype), sds((1,), jnp.float32), i32,
                 i32).compile()
    report(f"{tag} prefill chunk S={S}", c, time.perf_counter() - t)


def rehearse_glu(one, rows):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    K, N = 2048, 8192
    for m in rows:
        t = time.perf_counter()
        sds = lambda shp: jax.ShapeDtypeStruct(shp, jnp.bfloat16, sharding=one)
        fn = jax.jit(lambda x, g, u: ops.fused_glu(x, g, u))
        c = fn.lower(sds((m, K)), sds((K, N)), sds((K, N))).compile()
        report(f"glu_2d M={m} K={K} N={N}", c, time.perf_counter() - t)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="", help="comma-separated; default all")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0: full)")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = files.load_benchmark()
    want = set(filter(None, args.cells.split(",")))
    for cell in bench["workloads"]:
        if not want or cell["name"] in want:
            rehearse_cell(cell, one, args.layers)
    rehearse_glu(one, (32, 512))


if __name__ == "__main__":
    main()
