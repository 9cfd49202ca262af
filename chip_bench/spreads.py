#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over repeated runs, to set the
bounds in ``BENCHMARK.json``.

    python3 chip_bench/spreads.py setA.jsonl setB.jsonl

Each file holds the result lines (the last stdout line of
``chip_bench/run.py``) of one set of runs of one cell, one per line, the
two sets on the same seeds. For each metric it prints each set's median
and spread (first to third quartile over the median,
``statistics.quantiles(n=4)``), the wider spread, five times it (the
bound it suggests, never under 1%), and the second set's median against
the first's.
"""
from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chip_bench.stats import spread  # noqa: E402


def metrics(path):
    out = {}
    with open(path) as f:
        for line in f:
            if line.strip():
                for k, m in json.loads(line)["metrics"].items():
                    out.setdefault(k, []).append(m["value"])
    return out


def summary(paths):
    """{metric: (medians, spreads, suggested bound)} over the sets."""
    sets = [metrics(p) for p in paths]
    out = {}
    for name in sets[0]:
        vals = [s[name] for s in sets]
        sp = [spread(v) for v in vals]
        out[name] = ([statistics.median(v) for v in vals], sp,
                     max(0.01, 5 * max(sp)))
    return out


def main(paths) -> int:
    for name, (meds, sp, bound) in summary(paths).items():
        print(f"{name}: medians {meds}, spreads {sp}, widest "
              f"{max(sp):.4f}, suggested bound {bound:.4f}, "
              f"second/first median {meds[-1] / meds[0]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
