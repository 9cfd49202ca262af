"""Seeded traffic: request sizes drawn from a length law.

Every seed gets the same set of sizes, in another order: the sizes are the
law's quantiles at evenly spaced probabilities, and the seed only shuffles
them and draws the token ids. So two seeds ask for the same total work,
and a difference between seeds is not a difference in load.

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


def request_sizes(mix: Dict, rng: np.random.Generator) -> List[tuple]:
    """(prompt_len, output_len) pairs for ``mix["requests"]`` requests: the
    prompt and output quantiles are shuffled independently by ``rng``."""
    n = mix["requests"]
    prompts = rng.permutation(law_quantiles(mix["prompt"], n))
    outputs = rng.permutation(law_quantiles(mix["output"], n))
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def prompts(sizes: List[tuple], vocab: int,
            rng: np.random.Generator) -> List[np.ndarray]:
    """Token ids for each request, uniform over the vocabulary."""
    return [rng.integers(0, vocab, p, dtype=np.int32) for p, _ in sizes]

