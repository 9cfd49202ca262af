"""Whole-step share of the chip's bf16 peak: the dense-equivalent forward
operations of every position the traced waves processed (prompt and
generated), each attending over the positions up to it, over the window
and the peak (``chip_bench/flops.py``)."""
from chip_bench import flops


def read(r):
    n, ctx = r.counts.get("processed", 0), r.counts.get("context", 0)
    if not n:
        return None
    c = r.cell.config
    base = flops.lm_token(c, 0)
    ops = n * base + ctx * (flops.lm_token(c, 1) - base)
    return 100.0 * ops / r.trace.window_s / r.trace.devices \
        / r.peaks["bf16_flops_per_s"]
