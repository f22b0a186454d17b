#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip:

    python3 benchmarks/chip/sweep.py --workload qwen3-0.6b.chat \
        --rates 2,4,6,8 --seconds 15 --seed 1

One process builds the cell's engine and serves the cell's traffic at
each rate in turn (its own warm-up, a window, and the drain), printing
per rate: requests due and finished, time to first token p50/p95, time
per output token p95, tokens/s completed, the queue left at the end of
the window, and the high-water mark of the page pool so far. The knee
is the highest rate whose queue does not grow through the window; the
cell's rate is set to about four fifths of it. The sweep stops at the
first rate that leaves more than ``slots`` requests queued. The
benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import bench, files, readers  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402


def main(argv=None, *, root=files.ROOT, bench_doc=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    b = files.load_benchmark() if bench_doc is None else bench_doc
    cell = files.workload(b, args.workload)
    conf = files.config_of(cell["config"], root)
    mix = files.traffic_of(cell["traffic"], root)
    import jax
    if require_tpu:
        bench._use_cache(jax)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import program
    cfg = program.model_config(conf)
    engine = program.make_engine(cfg, program.make_params(cfg, args.seed),
                                 mix["engine"])
    program.warm_up(engine, conf["vocab_size"], args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate)
        drv = bench.Driver(engine, Traffic(m, conf["vocab_size"], args.seed),
                           conf, m)
        out = bench.serve(drv, args.seconds, 0, None)
        queue_end = len(engine.sched.queue)
        run = bench.Run(cell=cell, conf=conf, mix=m, peaks=None,
                        slots=engine.ecfg.slots, setup_s=0,
                        window=(out["a"], out["b"]),
                        due_window=out["due_window"],
                        records=list(drv.recs.values()), steps=drv.steps,
                        tokens_in_window=out["tok_b"] - out["tok_a"],
                        counters=bench.counters(out["stats_a"], out["stats_b"]),
                        window_compiles=0)
        due = run.due_in_window()
        ttft = [(r.first - r.due) for r in due if r.done]
        ttft.sort()
        print(json.dumps({
            "rate_per_s": rate, "cycle_s": drv.traffic.span, "due": len(due),
            "finished": sum(r.done for r in due),
            "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2] if ttft else None,
            "ttft_p95_ms": readers.ttft_p95_ms(run),
            "tpot_p95_ms": readers.tpot_p95_ms(run),
            "queue_wait_p95_ms": readers.queue_wait_p95_ms(run),
            "output_tok_s": readers.output_tok_s(run),
            "step_ms": readers.step_ms(run),
            "decode_occupancy": readers.decode_occupancy(run),
            "queue_at_window_end": queue_end,
            "pages_peak": engine.stats.pages_peak,
            "pages_total": engine.ecfg.n_pages}), flush=True)
        if queue_end > engine.ecfg.slots:
            break                      # past the knee: higher rates add nothing
        engine.sched.queue.clear()
        engine.run()
        engine.completions.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
