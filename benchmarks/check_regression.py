"""Bench-regression gate: fresh smoke artifacts vs committed baselines.

Four PRs of bench artifacts have been *uploaded* by CI without anything
reading them; this script makes CI *gate* on them. It extracts the
deterministic metrics from ``artifacts/*.json`` (skip fractions, modeled
speedups, MAC reductions, footprint compression, schedule agreement,
wave reductions — never wall-clock, which is CI noise), compares each
against the committed view in ``benchmarks/baselines/``, and exits
non-zero on drift outside the stated tolerances.

The extracted metrics are deterministic on any backend: they derive from
fixed PRNG seeds and modeled/counted quantities (occupancy maps, bucket
schedules, byte counts, wave counts), not from timing. Baselines are the
*smoke* variants CI produces; regenerate them after an intentional
change with

    PYTHONPATH=src python benchmarks/run.py --smoke
    PYTHONPATH=src python benchmarks/serve_bench.py --smoke
    python benchmarks/check_regression.py --update-baselines

Comparison rules: every baseline metric must exist in the fresh artifact
and sit within tolerance (a vanished metric IS drift); fresh metrics
absent from the baseline are ignored, so local full (non ``--smoke``)
runs — a superset of the smoke sweep — still pass. Two extra guards:
baseline key families outside the artifact's ``KNOWN_PREFIXES``
registry fail loud as *stale baselines* (the bench stopped emitting
that family — one targeted failure naming it, not a generic "vanished"
line per key), and ``FLOORS`` are absolute acceptance thresholds
checked against the fresh artifact itself — they hold even at
``--update-baselines`` time, so a regeneration can never ratify a
below-floor value. To keep that superset
property, only *sweep-independent* metrics are gated: per-row keys (a
full sweep adds rows, never changes a smoke row) and whole-config
quantities (footprint compression, PTQ logit MAE, wave reduction) —
never sweep aggregates like maxima or means over however many points
happened to run.

Usage: python benchmarks/check_regression.py [--artifacts DIR]
           [--baselines DIR] [--update-baselines]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# tolerance kinds: ("abs", x) -> |fresh - base| <= x;
#                  ("rel", x) -> |fresh - base| <= x * max(|base|, eps);
#                  ("exact",) -> fresh == base
ABS, REL, EXACT = "abs", "rel", "exact"


def _row_key(prefix, row, fields):
    parts = [prefix] + [str(row[f]).replace(" ", "") for f in fields]
    return "/".join(parts)


def extract_dual_engine(blob):
    """Sparse-engine sweep: per-point tile skip + modeled speedup, the
    tile-vs-decoded ragged-pattern rows, and the layer program's
    measured overlap (executed-step counts + schedule ratios)."""
    out = {}
    for r in blob.get("rows", []):
        key = _row_key("linear", r, ("shape", "block", "sparsity"))
        out[key + "/skip_fraction"] = (r["skip_fraction"], (ABS, 0.02))
        out[key + "/modeled_speedup"] = (r["modeled_speedup"], (REL, 0.05))
    for r in blob.get("sparse_path_rows", []):
        key = _row_key("sparse_path", r, ("pattern", "shape"))
        out[key + "/tile_skip_fraction"] = (
            r["tile_skip_fraction"], (ABS, 0.02))
        out[key + "/decoded_mac_reduction"] = (
            r["decoded_mac_reduction"], (ABS, 0.03))
        out[key + "/decoded_modeled_speedup"] = (
            r["decoded_modeled_speedup"], (REL, 0.05))
        out[key + "/sched_agreement"] = (r["sched_agreement"], (ABS, 0.15))
    for r in blob.get("layer_rows", []):
        # layer-program step: everything here derives from the kernel's
        # (H, 8, n_l_blocks) occupancy-map counts on fixed-seed inputs —
        # deterministic on any backend. Counts are gated exactly,
        # schedule ratios with float tolerance, the sim twin's
        # binary-phase prediction pinned sub-block-exact. The `off` rows
        # are the sequential oracle baseline — wall clock only, never
        # gated.
        if r["overlap"] == "off":
            continue
        key = _row_key("layer", r, ("config", "overlap", "sparse"))
        for ph in ("q", "k", "v", "qkt", "qktv", "wo", "up", "down"):
            out[key + f"/executed_{ph}"] = (r[f"executed_{ph}"], (EXACT,))
        for f in ("executed_steps", "possible_steps", "sim_binary_exact"):
            out[key + f"/{f}"] = (r[f], (EXACT,))
        for f in ("hidden_fraction", "qkt_hidden_fraction",
                  "qktv_hidden_fraction", "step_reduction",
                  "sim_binary_agreement"):
            out[key + f"/{f}"] = (r[f], (ABS, 0.02))
    # derived aggregates (max/mean over the sweep, auto-win counts) are
    # deliberately NOT gated: they change with the sweep size, so a full
    # run would spuriously drift vs a smoke baseline — the per-row keys
    # above carry the same information robustly.
    return out


def extract_quant(blob):
    """Quantized datapath: footprint compression (byte-counted, tight
    tolerance) and PTQ logit fidelity (spike-flip dominated, loose)."""
    out = {}
    fp = blob.get("footprint", {})
    for dtype in ("int8", "int4"):
        if dtype in fp:
            out[f"footprint/{dtype}/compression"] = (
                fp[dtype]["compression"], (REL, 0.005))
            out[f"footprint/{dtype}/total_compression"] = (
                fp[dtype]["total_compression"], (REL, 0.005))
    d = blob.get("derived", {})
    for arch, mae in d.get("int8_logit_mae_rel", {}).items():
        out[f"derived/int8_logit_mae_rel/{arch}"] = (mae, (ABS, 0.1))
    return out


def extract_serve(blob):
    """Serve orchestrator: chunked-prefill wave reduction per arch (a
    scheduler-counted quantity, not a timing)."""
    out = {}
    d = blob.get("derived", {})
    for arch, red in d.get("wave_reduction_chunked_vs_1", {}).items():
        out[f"derived/wave_reduction_chunked_vs_1/{arch}"] = (
            red, (ABS, 0.1))
    return out


SPECS = {
    "dual_engine_bench.json": extract_dual_engine,
    "quant_bench.json": extract_quant,
    "serve_bench.json": extract_serve,
}

# every key family (first path segment) an extractor can emit. A
# committed baseline key outside its artifact's registry means the
# bench stopped emitting that family entirely (renamed or removed):
# fail loud with the family named, instead of one generic "vanished"
# line per key, so the fix (regenerate baselines or restore the bench)
# is obvious.
KNOWN_PREFIXES = {
    "dual_engine_bench.json": ("linear", "sparse_path", "layer"),
    "quant_bench.json": ("footprint", "derived"),
    "serve_bench.json": ("derived",),
}

# acceptance floors checked against the *fresh* artifact (and at
# --update-baselines time), independent of the committed baseline — a
# baseline regeneration must never ratify a value below the floor. The
# layer floor: the whole-layer program's measured binary-hidden fraction
# on the token config strictly exceeds 0.3971, what the SSA bundle alone
# (Q/K/V projections + attention, without the MLP tail) hid on the same
# workload.
FLOORS = {
    "dual_engine_bench.json": (
        ("layer/spikingformer-lm/fused/tile/hidden_fraction", 0.3971),
    ),
}


def _within(fresh, base, tol):
    if tol[0] == EXACT:
        return fresh == base
    if not isinstance(fresh, (int, float)) or isinstance(fresh, bool):
        return fresh == base
    if tol[0] == ABS:
        return abs(fresh - base) <= tol[1]
    return abs(fresh - base) <= tol[1] * max(abs(base), 1e-9)


def check(artifacts_dir: str, baselines_dir: str, update: bool) -> int:
    failures, checked = [], 0
    if update:
        # validate the whole artifact set BEFORE writing anything: a
        # partial update would leave a mixed fresh/stale baselines dir
        missing = [n for n in SPECS
                   if not os.path.exists(os.path.join(artifacts_dir, n))]
        if missing:
            for n in missing:
                print(f"  FAIL {n}: artifact missing in {artifacts_dir}")
            print("no baselines written — run the smoke benches for the "
                  "missing artifacts first.")
            return 1
    for name, extract in SPECS.items():
        apath = os.path.join(artifacts_dir, name)
        bpath = os.path.join(baselines_dir, name)
        if not os.path.exists(apath):
            failures.append(f"{name}: artifact missing at {apath} "
                            f"(run the smoke benches first)")
            continue
        try:
            with open(apath) as f:
                pairs = extract(json.load(f))
        except (KeyError, TypeError, AttributeError,
                json.JSONDecodeError) as e:
            failures.append(f"{name}: stale or malformed artifact "
                            f"({type(e).__name__}: {e}) — regenerate "
                            f"with the smoke benches")
            continue
        fresh = {k: v for k, (v, _) in pairs.items()}
        tols = {k: t for k, (_, t) in pairs.items()}
        floor_fails = []
        for key, floor in FLOORS.get(name, ()):
            if key not in fresh:
                floor_fails.append(f"{name}:{key}: floor metric missing "
                                   f"(must be strictly above {floor})")
            elif not fresh[key] > floor:
                floor_fails.append(f"{name}:{key}: {fresh[key]} is not "
                                   f"strictly above the floor {floor}")
        failures.extend(floor_fails)
        checked += len(FLOORS.get(name, ()))
        if update:
            if floor_fails:
                continue      # never ratify a below-floor artifact
            os.makedirs(baselines_dir, exist_ok=True)
            with open(bpath, "w") as f:
                json.dump(fresh, f, indent=1, sort_keys=True)
            print(f"updated {bpath} ({len(fresh)} metrics)")
            continue
        if not os.path.exists(bpath):
            failures.append(f"{name}: no committed baseline at {bpath} "
                            f"(run with --update-baselines and commit)")
            continue
        with open(bpath) as f:
            base = json.load(f)
        known = KNOWN_PREFIXES.get(name)
        if known is not None:
            for fam in sorted({k.split("/", 1)[0] for k in base}
                              - set(known)):
                n = sum(1 for k in base if k.split("/", 1)[0] == fam)
                failures.append(
                    f"{name}: stale baseline family '{fam}' ({n} keys) "
                    f"— no bench emits this prefix anymore; regenerate "
                    f"baselines (--update-baselines) and commit")
        for key, bval in sorted(base.items()):
            if known is not None and key.split("/", 1)[0] not in known:
                continue      # reported above as a stale family
            checked += 1
            if key not in fresh:
                failures.append(f"{name}:{key}: metric vanished "
                                f"(baseline {bval})")
                continue
            tol = tols.get(key, (EXACT,))
            if not _within(fresh[key], bval, tol):
                failures.append(
                    f"{name}:{key}: {fresh[key]} vs baseline {bval} "
                    f"(tol {tol})")
    if update:
        if failures:  # e.g. a malformed artifact surfaced mid-update
            for f in failures:
                print(f"  FAIL {f}")
            print("baselines NOT fully updated — fix the artifacts "
                  "above and rerun.")
            return 1
        return 0
    if failures:
        print(f"BENCH REGRESSION: {len(failures)} of {checked} gated "
              f"metrics drifted:")
        for f in failures:
            print(f"  FAIL {f}")
        print("If the drift is intentional, regenerate baselines "
              "(--update-baselines after the smoke benches) and commit.")
        return 1
    print(f"bench regression gate: {checked} metrics within tolerance "
          f"across {len(SPECS)} artifacts")
    return 0


def main():
    ap = argparse.ArgumentParser()
    here = os.path.dirname(os.path.abspath(__file__))
    ap.add_argument("--artifacts", default=os.path.join(here, "..",
                                                        "artifacts"))
    ap.add_argument("--baselines", default=os.path.join(here, "baselines"))
    ap.add_argument("--update-baselines", action="store_true")
    args = ap.parse_args()
    sys.exit(check(args.artifacts, args.baselines, args.update_baselines))


if __name__ == "__main__":
    main()
