"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per bench plus the full row dumps.

Usage: PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the model-training sparsity bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: same as --fast")
    args = ap.parse_args()
    if args.smoke:
        args.fast = True

    import dual_engine_bench
    import paper_figures as pf
    import quant_bench

    quant_extras = []

    def quant_fn():
        rows, extras = quant_bench.bench(fast=args.fast)
        quant_extras.append((rows, extras))
        return rows, extras["derived"]

    benches = [
        ("fig12_decoder", pf.fig12_decoder),
        ("fig13_balance", pf.fig13_balance),
        ("table4_comparison", pf.table4_comparison),
        ("table56_resources", pf.table56_resources),
        ("fig5_pipeline", pf.fig5_pipeline),
        ("kernels", pf.kernels_bench),
        ("dual_engine", lambda: dual_engine_bench.bench(fast=args.fast)),
        ("quant", quant_fn),
    ]
    if not args.fast:
        benches.insert(0, ("fig11_sparsity", pf.fig11_sparsity))

    print("name,us_per_call,derived")
    all_rows = {}
    for name, fn in benches:
        t0 = time.perf_counter()
        rows, derived = fn()
        us = (time.perf_counter() - t0) * 1e6
        all_rows[name] = {"rows": rows, "derived": derived}
        print(f"{name},{us:.0f},\"{json.dumps(derived)}\"")
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/bench_results.json", "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    # standalone dual-engine artifact (matmul + attention sweeps): same
    # layout dual_engine_bench.py --out writes, kept current by every run
    de = all_rows["dual_engine"]
    with open("artifacts/dual_engine_bench.json", "w") as f:
        json.dump(dual_engine_bench.to_blob(de["rows"], de["derived"]),
                  f, indent=1)
    # standalone quantization artifact (kernel sweep + measured footprint
    # + PTQ calibration): same layout quant_bench.py --out writes
    q_rows, q_extras = quant_extras[0]
    with open("artifacts/quant_bench.json", "w") as f:
        json.dump(quant_bench.to_blob(q_rows, q_extras), f, indent=1)

    print("\n== row dumps ==")
    for name, blob in all_rows.items():
        for row in blob["rows"]:
            print(json.dumps(row))


if __name__ == "__main__":
    main()
