#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 chip_bench/run.py --workload sf8-512.classify --seed 7 \
        --seconds 30 --trace 0

Without ``--trace`` (0) the result line carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the last seconds of the window. Either way the run checks what
the timed path produced against the plain float32 reference, prints each
compared number beside its limit as its last lines on standard error,
and prints one JSON object as the last line of standard output. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chip_bench import harness
    cell = harness.Cell(harness.load_json("BENCHMARK.json"), args.workload,
                        args.seed)
    devices = harness.require_accelerator(cell.chips)
    harness.compile_cache()
    result = run_cell(cell, devices, args.seconds, bool(args.trace))
    checks = result.pop("checks")
    for name, (value, limit) in checks.items():
        harness.log(f"check {name}: {value!r} (limit {limit!r})")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, devices, seconds: float, trace: bool) -> dict:
    """Set-up, window, metrics and checks of one run; the result dict."""
    from chip_bench import harness

    harness.log(f"cell {cell.name} seed {cell.seed} on "
                f"{len(devices)} x {devices[0].device_kind}")
    runner_mod = harness.load_module(
        harness.HERE / "runners" / f"{cell.traffic['runner']}.py",
        "chip_bench_runner")
    runner = runner_mod.Runner(cell, devices)
    t_ready = time.perf_counter() - T_START
    runner.setup()
    setup_s = time.perf_counter() - T_START
    harness.log(f"set-up {setup_s:.3f} s ({t_ready:.3f} s to import and "
                f"find the devices, the rest weights and warm-up)")

    compiles = harness.CompileCounter()
    t0, t1, traced, capture = harness.measure(runner, seconds, trace,
                                              compiles)
    harness.log(f"window {t1 - t0:.3f} s; programs traced or compiled "
                f"inside it: {len(compiles.names)} {compiles.names}")
    peak = harness.peak_memory(devices)
    harness.log(f"peak HBM {peak} bytes (memory_stats)")

    kind = devices[0].device_kind
    metrics = {}
    breakdown = None
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from chip_bench.trace import Trace
        tr = Trace(capture.load(runner.scopes()))
        reading = harness.Reading(tr, traced, cell,
                                  harness.peaks_for(kind))
        metrics = harness.read_per_layer(cell, reading)
        dev.update(busy_s=tr.busy_s, window_s=tr.window_s)
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    else:
        e2e = runner.end_to_end(t0, t1)
        e2e["setup_s"] = setup_s
        # undeclared ones too, such as a tail too unsteady to gate on
        harness.log("end to end: " + ", ".join(
            f"{k} {v!r}" for k, v in e2e.items()))
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    for line in runner.notes():
        harness.log(line)

    attempted, failed = runner.attempted()
    runner.free()
    t_check = time.perf_counter()
    numbers, densities = runner.check()
    harness.log(f"reference check {time.perf_counter() - t_check:.3f} s; "
                f"numbers {numbers}")
    harness.log("spike density per layer: " + " ".join(
        f"{k}={v:.4f}" for k, v in densities.items()))
    checks, correct = decide(cell, numbers)
    correct = correct and failed == 0
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def decide(cell, numbers: dict):
    """The cell's limits (``limits/<cell>.json``) applied to the numbers a
    check read: ({name: (value, limit)}, whether every value is within
    its limit)."""
    from chip_bench import harness
    limits = harness.load_json(f"chip_bench/limits/{cell.name}.json")
    checks = {k: (numbers[k], lim) for k, lim in limits["limits"].items()}
    return checks, all(v <= lim for v, lim in checks.values())


if __name__ == "__main__":
    sys.exit(main())
