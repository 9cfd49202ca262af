"""Smoke-size cells for the CPU rehearsal of the chip benchmark: the same
runners and checks at the registry's SMOKE sizes, with the harness's look
for a chip left out."""
from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

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
                     "max_len": 48, "chunk": 0, "requests": 16,
                     "lead_in_s": 0.5,
                     "prompt": {"law": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 3, "max": 16},
                     "output": {"law": "lognormal", "median": 6,
                                "sigma": 0.5, "min": 2, "max": 12},
                     "check": 3},
}

# A cell whose files are in the tree but which BENCHMARK.json does not
# declare until it has been measured on the chip: its rehearsals run all
# the same.
PENDING = {
    "sflm.batch": {
        "workload": {"name": "sflm.batch", "config": "spikingformer-lm",
                     "traffic": "sharegpt_closed128", "chips": 1},
        "end_to_end": [
            {"name": n, "unit": u, "workloads": ["sflm.batch"]}
            for n, u in (("tokens_per_s", "tokens/s"),
                         ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"))]},
}


class SmokeCell:
    """A harness ``Cell`` of one real workload, its model cut to SMOKE
    sizes unless ``full_width``, its traffic cut to a few requests."""

    def __init__(self, name: str, seed: int = 5, full_width: bool = False):
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        for cell, entry in PENDING.items():
            if cell not in {x["name"] for x in bench["workloads"]}:
                bench["workloads"].append(entry["workload"])
                bench["end_to_end"] += entry["end_to_end"]
        w = {x["name"]: x for x in bench["workloads"]}[name]
        self.config = json.loads((ROOT / "chip_bench" / "configs"
                                  / f"{w['config']}.json").read_text())
        if not full_width:
            self.config.update(VISION_SMOKE if self.config["family"]
                               == "spikingformer" else LM_SMOKE)
        runner = json.loads((ROOT / "chip_bench" / "traffic"
                             / f"{w['traffic']}.json").read_text())["runner"]
        self.traffic = copy.deepcopy(TRAFFIC[runner])
        self.name, self.seed, self.chips = name, seed, 1
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
