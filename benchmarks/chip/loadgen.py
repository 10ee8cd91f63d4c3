"""One general generator of serving traffic, driven by a traffic file.

A traffic file gives the arrival process and the length distributions;
this module turns them and a seed into a schedule of requests.  Every
seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified draws: the i-th of n values is the
distribution's quantile at (i + 0.5) / n), in an order and with token ids
that the seed decides.  So two seeds offer the same work, and the spread
between runs measures the system rather than the draw.

The order is stratified too, by the traffic file's ``order_block`` B:
the requests come in blocks of B (or B - 1), and each block holds one
value from each of B rank strata of every list (one of the longest
answers, one of the shortest gaps, ...), shuffled within the block.
Each position still draws from the whole distribution, but no seed
piles its long answers or its short gaps into one stretch of the
window, which would decide a tail by the order alone.  A B as large as
the schedule leaves a plain permutation.

The open-loop arithmetic (exponential gaps at a fixed rate, requests due
whether or not earlier ones finished) follows
``repro.serve.fabric.traffic.poisson_trace``; lengths here are
heavy-tailed instead of uniform over a few values, and times are seconds
on the wall clock instead of virtual nanoseconds.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Due:
    """One request of the schedule: due ``t`` seconds after the schedule
    starts (the pre-roll starts at 0, the measured window at
    ``preroll_s``)."""

    idx: int
    t: float
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified lengths of a ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` spec."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps (seconds) of the arrival spec."""
    rate = float(spec["rate_per_s"])
    if spec["kind"] == "poisson":
        return -np.log1p(-_quantiles(n)) / rate
    raise ValueError(f"unknown arrival process {spec['kind']!r}")


def blocks(values: np.ndarray, rng: np.random.Generator, block: int
           ) -> List[np.ndarray]:
    """``values`` dealt into ``ceil(n / block)`` blocks: the sorted values
    cut into rank strata of one value per block, each stratum dealt at
    random (the last, short stratum leaves some blocks one short), each
    block shuffled."""
    vals = np.sort(values)
    n_blocks = -(-len(vals) // max(1, block))
    dealt = [[] for _ in range(n_blocks)]
    for lo in range(0, len(vals), n_blocks):
        for v, b in zip(vals[lo:lo + n_blocks], rng.permutation(n_blocks)):
            dealt[b].append(v)
    return [rng.permutation(np.asarray(b)) for b in dealt if b]


def order(values: np.ndarray, rng: np.random.Generator, block: int
          ) -> np.ndarray:
    """``values`` in an order drawn from ``rng``: its ``blocks`` one after
    another."""
    return np.concatenate(blocks(values, rng, block))


def schedule(traffic: dict, seed: int, seconds: float, vocab: int
             ) -> List[Due]:
    """The requests due from the start of the pre-roll to the end of a
    ``seconds``-long window, for ``seed``."""
    span = float(traffic["preroll_s"]) + float(seconds)
    n = max(1, int(round(span * float(traffic["arrivals"]["rate_per_s"]))))
    rng = np.random.default_rng(seed)
    block = int(traffic["order_block"])
    prompt_lens = order(lengths(traffic["prompt_len"], n), rng, block)
    output_lens = order(lengths(traffic["output_len"], n), rng, block)
    due = np.cumsum(order(gaps(traffic["arrivals"], n), rng, block))
    # the stratified gaps sum to the span up to rounding of n; pin the
    # schedule's end to the window's end so every seed offers its work
    # over the same time
    due *= span / due[-1]
    due = np.concatenate([[0.0], due[:-1]])
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(prompt_lens[i]), dtype=np.int32)
        out.append(Due(idx=i, t=float(due[i]), prompt=prompt,
                       max_new_tokens=int(output_lens[i])))
    return out
