"""Share (%) of its roofline that the grouped expert matmuls reach: the
least time the chip could take for their operations and bytes
(``chip_bench/flops_moe.py``: the real rows processed and the held
experts' weights once a wave and layer), the larger of operations over
the bf16 peak and bytes over the HBM peak, over the device time under
``moe.experts``."""
from chip_bench import flops_moe


def read(r):
    rows, waves = r.counts.get("processed", 0), r.counts.get("waves", 0)
    scoped = r.trace.scope_s("moe.experts")
    if not rows or not waves or not scoped:
        return None
    c = r.cell.config
    least = max(flops_moe.gmm_ops(c, rows) / r.peaks["bf16_flops_per_s"],
                flops_moe.gmm_bytes(c, rows, waves)
                / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / scoped
