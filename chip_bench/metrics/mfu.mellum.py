"""Whole-step share of the chip's bf16 peak: the forward operations of
every position the traced waves processed (``chip_bench/flops_moe.py``:
projections, router, the held experts' expected share, the LM head),
each attending over the positions up to it (at most ``sliding_window`` on
the sliding layers), over the window and the peak."""
from chip_bench import flops_moe


def read(r):
    n = r.counts.get("processed", 0)
    if not n:
        return None
    c = r.cell.config
    ops = n * flops_moe.token(c) \
        + flops_moe.layers_of(c, "full_attention") \
        * flops_moe.attend(c, r.counts.get("context", 0)) \
        + flops_moe.layers_of(c, "sliding_attention") \
        * flops_moe.attend(c, r.counts.get("context_window", 0))
    return 100.0 * ops / r.trace.window_s / r.trace.devices \
        / r.peaks["bf16_flops_per_s"]
