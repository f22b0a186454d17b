"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<name>.json``); nothing about a mix is code.

Keys of a mix:

    kind        "open_loop": Poisson arrivals at ``rate_per_s``, sent on
                schedule whatever the server does; "backlog": a closed
                queue kept at least ``queue_depth`` deep all run
    prompt      {"dist": "lognormal", "median", "sigma", "min", "max"} or
    output      {"dist": "uniform", "min", "max"}: token counts, clipped
    cycle       requests in one cycle of the stream (below)
    sizes_seed  seed of the pairing of prompt and output lengths and of
                the order of each cycle
    engine      the serving engine's sizes for this mix
    warmup_s    seconds of this traffic before the measured window (open
                loop: rounded up to whole cycles)
    drain_cap_s how long requests due in the window are followed after it
    check       how many finished requests the correctness check samples

The stream is a run of cycles. Every cycle holds the same ``cycle``
(prompt, output) pairs and, in an open loop, the same inter-arrival
gaps: the ``cycle`` quantiles of each length distribution, paired by a
shuffle drawn from ``sizes_seed``, and the quantiles of the exponential
distribution scaled so that a cycle spans exactly
``cycle / rate_per_s`` seconds (Poisson arrivals, evenly sampled).
Cycle ``c`` takes the pairs and the gaps in an order drawn from
``sizes_seed`` and ``c``, so cycles differ from each other and the
schedule of sizes and due times is the same for every ``--seed``, which
draws only the token content. So every seed does the same work at the
same times, and no two requests share content (no prefix-cache hits by
accident). A mix with another ``sizes_seed`` is another schedule.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt_len: int
    max_new: int
    due: float | None          # seconds after the start of traffic (open
                               # loop), None in a backlog
    tokens: np.ndarray | None = None


def quantiles(n: int) -> np.ndarray:
    """The n mid-points (i + 1/2) / n of [0, 1]."""
    return (np.arange(n) + 0.5) / n


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The distribution's n quantiles at ``quantiles(n)``, rounded and
    clipped: a cycle that spans the distribution evenly."""
    lo, hi = int(spec["min"]), int(spec["max"])
    q = quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


class Traffic:
    """An endless, seeded request stream for one mix.

    ``next()`` returns requests in order; content is drawn as each one is
    taken, so the stream never runs out and never repeats a prompt."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        self.mix = mix
        self.kind = mix["kind"]
        if self.kind not in ("open_loop", "backlog"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        n = int(mix["cycle"])
        base = np.random.default_rng(int(mix["sizes_seed"]))
        self.prompt_lens = quantile_lengths(mix["prompt"], n)
        self.out_lens = quantile_lengths(mix["output"], n)[base.permutation(n)]
        if self.kind == "open_loop":
            self.span = n / float(mix["rate_per_s"])
            gaps = -np.log1p(-quantiles(n))          # exponential quantiles
            self.gaps = gaps * (self.span / gaps.sum())
        else:
            self.span = None
            self.gaps = np.zeros(n)
        self.rng = np.random.default_rng(int(seed))
        self.vocab_size = int(vocab_size)
        self.i = 0
        self.order = None
        self.offsets = None

    def _new_cycle(self, c: int):
        n = len(self.prompt_lens)
        rng = np.random.default_rng([int(self.mix["sizes_seed"]), 1, c])
        self.order = rng.permutation(n)
        g = self.gaps[rng.permutation(n)]
        self.offsets = np.cumsum(g) - g[0]     # first request on the boundary

    def next(self) -> Request:
        n = len(self.prompt_lens)
        c, k = divmod(self.i, n)
        if k == 0:
            self._new_cycle(c)
        j = int(self.order[k])
        due = (c * self.span + float(self.offsets[k])
               if self.kind == "open_loop" else None)
        req = Request(index=self.i, prompt_len=int(self.prompt_lens[j]),
                      max_new=int(self.out_lens[j]), due=due)
        req.tokens = self.rng.integers(0, self.vocab_size,
                                       size=req.prompt_len, dtype=np.int32)
        self.i += 1
        return req
