"""Smoke-size cells for the CPU rehearsal of the chip benchmark: the same
runners and checks at the registry's SMOKE sizes, with the harness's look
for a chip left out."""
from __future__ import annotations

import copy

from chip_bench import harness

VISION_SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "head_dim": 16, "d_ff": 128, "vocab_size": 10,
                "img_size": 32, "time_steps": 2, "dtype": "float32",
                "sps_channels": [8, 16, 32, 64], "smoke": True}
LM_SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
            "vocab_size": 64, "time_steps": 2, "dtype": "float32",
            "smoke": True}
TRAFFIC = {
    "classify": {"runner": "classify", "batch": 4, "batches": 2,
                 "check": 8},
    "serve_closed": {"runner": "serve_closed", "clients": 3, "slots": 3,
                     "max_len": 48, "chunk": 4, "requests": 16,
                     "lead_in_s": 0.5,
                     "prompt": {"law": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 3, "max": 16},
                     "output": {"law": "lognormal", "median": 6,
                                "sigma": 0.5, "min": 2, "max": 12},
                     "check": 3},
}


class SmokeCell(harness.Cell):
    """A harness ``Cell`` of one real workload, its model cut to SMOKE
    sizes unless ``full_width``, its traffic cut to a few requests."""

    def __init__(self, name: str, seed: int = 5, full_width: bool = False):
        super().__init__(harness.load_json("BENCHMARK.json"), name, seed)
        if not full_width:
            self.config.update(VISION_SMOKE if self.config["family"]
                               == "spikingformer" else LM_SMOKE)
        self.traffic = copy.deepcopy(TRAFFIC[self.traffic["runner"]])
        self.chips = 1
