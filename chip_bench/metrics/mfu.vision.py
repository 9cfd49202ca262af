"""Whole-step share of the chip's bf16 peak: the dense-equivalent forward
operations of every image finished in the traced window, over the window
and the peak (``chip_bench/flops.py``)."""
from chip_bench import flops


def read(r):
    images = r.counts.get("images", 0)
    if not images:
        return None
    ops = images * flops.vision_image(r.cell.config)
    return 100.0 * ops / r.trace.window_s / r.trace.devices \
        / r.peaks["bf16_flops_per_s"]
