#!/usr/bin/env python3
"""Readings that set a configuration's correctness limit, on the chip:

    python3 benchmarks/chip/calibrate.py --workload qwen3-0.6b.chat \
        --seeds 11,12,13 --seconds 8 [--control 1]

In one process, for each seed: the cell's weights and traffic from the
seed, the cell's engine at the cell's load for a short window (after the
mix's own warm-up) and its drain; then, with the engine freed, the
correctness sample of each seed through the float32 reference. Prints
per seed the widest gap of a served token below the reference's best
(the program's reading) and, with ``--control 1``, the widest gap of the
token that the float8 control ranks first at the same positions (the
control's reading). The limit lies between the largest program reading
and the smallest control reading. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import bench, files, program  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402


def main(argv=None, *, root=files.ROOT, bench_doc=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    b = files.load_benchmark() if bench_doc is None else bench_doc
    cell = files.workload(b, args.workload)
    conf = files.config_of(cell["config"], root)
    mix = files.traffic_of(cell["traffic"], root)
    import jax
    if require_tpu:
        bench._use_cache(jax)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg = program.model_config(conf)
    finished = {}
    engine = None
    for seed in seeds:
        t = time.perf_counter()
        params = program.make_params(cfg, seed)
        if engine is None:
            engine = program.make_engine(cfg, params, mix["engine"])
            program.warm_up(engine, conf["vocab_size"], seed)
        else:
            engine.params = params
        del params
        drv = bench.Driver(engine, Traffic(mix, conf["vocab_size"], seed),
                           conf, mix)
        bench.serve(drv, args.seconds, 0, None)
        engine.sched.queue.clear()         # finish what is in the slots only
        engine.run()
        engine.completions.clear()
        finished[seed] = [r for r in drv.recs.values() if r.done]
        print(f"[serve] seed={seed} finished={len(finished[seed])} "
              f"wall_s={time.perf_counter() - t:.1f}", flush=True)
    engine.params = None
    del engine, drv
    for seed in seeds:
        ok, numbers, rows = bench.judge(conf, mix, finished[seed], seed, root,
                                        control=bool(args.control))
        print(json.dumps({
            "seed": seed, "tokens": sum(r["tokens"] for r in rows),
            "program_gap": max(r["gap"] for r in rows),
            "control_gap": (numbers["max_logit_gap"]["value"]
                            if args.control else None),
            "correct": ok, "limit": numbers["max_logit_gap"]["limit"],
            "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
