"""The one traffic generator: turns a mix's data file (``traffic/<mix>.json``)
and a seed into a schedule on wall time.

Every seed gets the same work: the same multiset of sizes and the same
arrival gaps, in another order, so two seeds differ in content and order
and not in how much there is to do.  Sizes come from ``[value, weight]``
lists: over ``n`` requests each value appears in proportion to its weight
(largest remainders round), then the seed shuffles them.

Kinds of mix:

* ``lm_closed_loop`` — ``clients`` streaming clients; each cycles through
  ``cycle`` requests of its own and sends the next when its answer has
  arrived.
* ``lm_open_loop`` — requests due at fixed times whatever the server does:
  Poisson arrivals whose mean rate follows ``phases`` (``[seconds,
  multiple of rate_per_s]``) over each period; each request gets a client
  of its own.
* ``camera_loop`` — ``cameras`` each keep one frame in flight, with the
  codecs listed in ``codecs``.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per use, from any non-negative seed (seeds
    above 32 bits included)."""
    return np.random.default_rng([seed, sum(map(ord, stream))])


def proportional(pairs: Sequence[Sequence], n: int) -> List:
    """``n`` values from ``[value, weight]`` pairs, each value in
    proportion to its weight, largest remainders rounded up; sorted."""
    w = np.asarray([p[1] for p in pairs], float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [p[0] for p, c in zip(pairs, counts) for _ in range(c)]


def size_pairs(mix: Dict, n: int, seed: int) -> List[Tuple[int, int]]:
    """``n`` (prompt length, generation length) pairs: the same pairs for
    every seed (a fixed pairing of the two proportional lists), in an
    order drawn from the seed."""
    lens = proportional(mix["prompt_lengths"], n)
    gens = rng_for(0, "pairing").permutation(
        proportional(mix["gen_lengths"], n))
    pairs = [(int(p), int(g)) for p, g in zip(lens, gens)]
    return [pairs[i] for i in rng_for(seed, "sizes").permutation(n)]


@dataclass
class Request:
    due: float            # seconds after the window opens (open loop)
    prompt: List[int]
    gen: int


def _prompt(rng: np.random.Generator, length: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, length).tolist()


def closed_loop_cycles(mix: Dict, seed: int, vocab: int
                       ) -> List[List[Request]]:
    """Per client, the requests it cycles through.  Each round (the k-th
    request of every client) holds the same multiset of sizes whatever the
    seed, so the window's first wave is the same work for every seed."""
    n = mix["clients"]
    toks = rng_for(seed, "tokens")
    rounds = [size_pairs(mix, n, seed * mix["cycle"] + k)
              for k in range(mix["cycle"])]
    return [[Request(0.0, _prompt(toks, p, vocab), g)
             for p, g in (rounds[k][i] for k in range(mix["cycle"]))]
            for i in range(n)]


def open_loop_schedule(mix: Dict, seed: int, seconds: float, vocab: int
                       ) -> List[Request]:
    """Requests due within ``seconds``: each phase of each period gets the
    arrivals its expected count adds to the running total (rounded, so the
    whole window offers ``rate_per_s`` on average), with gaps that are the
    exponential distribution's quantiles, shuffled by the seed."""
    rate = mix["rate_per_s"]
    rng = rng_for(seed, "arrivals")
    dues: List[float] = []
    t0 = expected = 0.0
    while t0 < seconds:
        for length, mult in mix["phases"]:
            span = min(length, seconds - t0)
            if span <= 0:
                break
            before = int(round(expected))
            expected += rate * mult * span
            n = int(round(expected)) - before
            if n:
                q = (np.arange(n) + 0.5) / n
                gaps = -np.log1p(-q)               # unit-mean exponential
                gaps = rng.permutation(gaps / gaps.sum() * span)
                starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
                dues.extend((t0 + starts).tolist())
            t0 += length
    dues = sorted(d for d in dues if d < seconds)
    toks = rng_for(seed, "tokens")
    return [Request(d, _prompt(toks, p, vocab), g)
            for d, (p, g) in zip(dues, size_pairs(mix, len(dues), seed))]


def camera_codecs(mix: Dict) -> List[str]:
    return proportional(mix["codecs"], mix["cameras"])


def frame_offsets(mix: Dict, seed: int) -> List[int]:
    """Each camera's first frame index, from the seed."""
    return rng_for(seed, "frames").integers(0, 1 << 20, mix["cameras"]).tolist()


def lengths_used(mix: Dict) -> List[int]:
    return sorted({int(p[0]) for p in mix.get("prompt_lengths", ())})


def lateness_summary(late: Sequence[float]) -> Tuple[float, float]:
    """(median, max) lateness of the generator, seconds."""
    if not late:
        return 0.0, 0.0
    return float(np.median(late)), float(max(late))


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of all values."""
    v = sorted(values)
    k = max(0, math.ceil(q / 100 * len(v)) - 1)
    return v[k]
