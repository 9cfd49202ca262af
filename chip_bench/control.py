#!/usr/bin/env python3
"""Readings that set a cell's check limits: the program's honest numbers
and the control's, over several seeds, in one process.

    python3 chip_bench/control.py --workload sflm.batch --seconds 12 \
        --seeds 11,12,13 --controls int8,float8_e4m3fn

For each seed: set-up as a benchmark run, a short window at the cell's own
load, then the checks of what the timed path produced (the honest reading)
and of each control at the same sample, each with ``correct`` as the
cell's committed limits decide it. One JSON line per seed. The limits are
set between the honest readings and the control's (PERF.md), and a
control has to read not correct; this is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def readings(cell, devices, seconds, controls):
    """{"honest": reading, <control>: reading} for one seed, after a
    window of ``seconds`` at the cell's load (``judge``)."""
    from chip_bench import harness
    runner = harness.load_module(
        harness.HERE / "runners" / f"{cell.traffic['runner']}.py",
        "chip_bench_runner").Runner(cell, devices)
    runner.setup()
    harness.measure(runner, seconds, False, harness.CompileCounter())
    return judge(cell, runner, controls)


def judge(cell, runner, controls):
    """The honest reading and each control's of what ``runner``'s window
    produced: the check's numbers with ``correct``, the cell's committed
    limits applied to them as a benchmark run applies them
    (``run.decide``)."""
    from chip_bench import run
    runner.free()
    out = {}
    for control in [None] + list(controls):
        numbers = runner.check(control)[0]
        out[control or "honest"] = dict(
            numbers, correct=run.decide(cell, numbers)[1])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8,float8_e4m3fn")
    args = ap.parse_args(argv)
    from chip_bench import harness
    harness.compile_cache()
    bench = harness.load_json("BENCHMARK.json")
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(bench, args.workload, seed)
        devices = harness.require_accelerator(cell.chips)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **readings(cell, devices, args.seconds,
                                     controls)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
