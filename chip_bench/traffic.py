"""Seeded traffic: request sizes drawn from a length law.

Every seed gets the same set of sizes, in another order: the sizes are the
law's quantiles at evenly spaced probabilities, and the seed only orders
them and draws the token ids. The order is even: any run of consecutive
requests is a near-even sample of both laws and of their pairing, so a
window that serves only a part of the requests asks for the same work
under every seed, and a difference between seeds is not a difference in
load.

A length law is a dict from a traffic file:
``{"law": "lognormal", "median": m, "sigma": s, "min": lo, "max": hi}``.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def law_quantiles(law: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the law's quantiles (i + 0.5) / n, clipped to
    [min, max] and rounded to whole tokens."""
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    if n < 1:
        raise ValueError(f"{n} lengths")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(math.log(law["median"]) + law["sigma"] * z)
    return np.clip(np.rint(raw), law["min"], law["max"]).astype(np.int64)


# steps of the two Kronecker sequences that order the prompt and the output
# quantiles: 1 and the two steps are linearly independent over the
# rationals, so the pairs spread evenly over the unit square
_STEPS = ((math.sqrt(5) - 1) / 2, math.sqrt(2) - 1)


def even_order(n: int, step: float, offset: float) -> np.ndarray:
    """A permutation of range(n) whose i-th entry is the rank of
    frac(offset + i * step) among the n such points: every run of
    consecutive entries spreads over the whole range, unlike a shuffle,
    whose runs of a few hundred vary by a tenth in their tails."""
    points = np.mod(offset + np.arange(n) * step, 1.0)
    return np.argsort(np.argsort(points, kind="stable"), kind="stable")


def request_sizes(mix: Dict, rng: np.random.Generator) -> List[tuple]:
    """(prompt_len, output_len) pairs for ``mix["requests"]`` requests: the
    prompt and output quantiles in even orders (``even_order``) whose
    offsets ``rng`` draws."""
    n = mix["requests"]
    offsets = rng.random(2)
    prompts, outputs = (
        law_quantiles(mix[k], n)[even_order(n, step, u)]
        for k, step, u in zip(("prompt", "output"), _STEPS, offsets))
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def prompts(sizes: List[tuple], vocab: int,
            rng: np.random.Generator) -> List[np.ndarray]:
    """Token ids for each request, uniform over the vocabulary."""
    return [rng.integers(0, vocab, p, dtype=np.int32) for p, _ in sizes]

