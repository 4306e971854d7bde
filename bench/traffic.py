"""The one traffic generator: reads a mix's parameters from
`bench/traffic/<mix>.json` and draws its requests from `--seed`.

Every seed gets the same set of sizes, in another order.  Requests come
in blocks of `block`: inside a block the prompt lengths are the grid
lengths in fixed counts (the mix's weights times the block, rounded by
largest remainder), and the output lengths are the block's evenly spaced
quantiles of the clipped lognormal.  The seed permutes each list inside
its block and draws the prompt tokens.  So any whole number of blocks
holds the same work for every seed, and the seed changes only its order
and its tokens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    prompt: List[int]
    max_new_tokens: int


def _grid_counts(weights, n: int) -> List[int]:
    """Largest-remainder rounding of `weights` (normalized) times n."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _lognormal_quantiles(median: float, sigma: float, lo: int, hi: int,
                         n: int) -> List[int]:
    from statistics import NormalDist

    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(hi, max(lo, round(median * math.exp(sigma * zi)))))
            for zi in z]


def block_sizes(mix: dict) -> List[tuple]:
    """The (prompt length, output length) pairs of one block, before the
    seed orders them."""
    n = mix["block"]
    prompts = []
    for length, count in zip(mix["prompt_grid"],
                             _grid_counts(mix["prompt_weights"], n)):
        prompts += [length] * count
    out = mix["output"]
    outputs = _lognormal_quantiles(out["median"], out["sigma"], out["min"],
                                   out["max"], n)
    return list(zip(prompts, outputs))


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Req]:
    """The mix's requests in order, without end."""
    rng = np.random.default_rng(seed)
    n = mix["block"]
    base = block_sizes(mix)
    prompts = [p for p, _ in base]
    outputs = [o for _, o in base]
    while True:
        p_order = rng.permutation(n)
        o_order = rng.permutation(n)
        for i in range(n):
            length = prompts[p_order[i]]
            yield Req(prompt=rng.integers(0, vocab, length).tolist(),
                      max_new_tokens=outputs[o_order[i]])

