"""The serving cell's control against its committed limits, on the CPU:
the published spikingformer-lm widths in the configuration's bfloat16,
two slots, and two requests whose outputs run to about 900 positions, as
the cell's longest do. The limits file is the one a benchmark run reads;
the float8 control (the reference computed in float8_e4m3fn) has to read
not correct, and the honest program correct, through the decision a run
makes (``control.judge`` -> ``run.decide``)."""
from __future__ import annotations

import jax

from chip_bench import control, harness
from chip_bench_smoke import SmokeCell

CELL = "sflm.batch"
TRAFFIC = {"runner": "serve_closed", "clients": 2, "slots": 2,
           "max_len": 1024, "chunk": 4, "requests": 8, "lead_in_s": 0,
           "prompt": {"law": "lognormal", "median": 64, "sigma": 0.3,
                      "min": 32, "max": 128},
           "output": {"law": "lognormal", "median": 800, "sigma": 0.1,
                      "min": 700, "max": 896},
           "check": 2}


def test_float8_control_fails_the_committed_limits():
    cell = SmokeCell(CELL, seed=8, full_width=True)
    cell.traffic = dict(TRAFFIC)
    runner = harness.load_module(
        harness.HERE / "runners" / "serve_closed.py", "control_runner").Runner(
        cell, jax.devices()[:1])
    runner.setup()
    while len(runner.finished) < TRAFFIC["check"]:
        runner.step()
    got = control.judge(cell, runner, ["float8_e4m3fn"])
    limit = harness.load_json(f"chip_bench/limits/{CELL}.json")["limits"]
    assert got["honest"]["correct"] is True, got
    assert got["float8_e4m3fn"]["correct"] is False, got
    for number in ("served_token_gap", "served_token_gap_mean"):
        assert got["float8_e4m3fn"][number] > limit[number] > \
            got["honest"][number], number
    assert got["honest"]["served_tokens_checked"] > 1000
