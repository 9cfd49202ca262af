"""The chip benchmark's yardstick on the CPU: operation counts, traffic
laws, percentile and rate arithmetic, the trace reductions, and the shape
of BENCHMARK.json. None of these numbers is a device measurement."""
from __future__ import annotations

import json
import math
import pathlib
import re
import statistics

import numpy as np
import pytest

from chip_bench import flops, stats, traffic, weights
from chip_bench.trace import Trace, clip, covered, hlo_scopes, op_label, \
    union

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((ROOT / "chip_bench" / "configs"
                       / f"{name}.json").read_text())


# -- operation counts ---------------------------------------------------------


def test_vision_image_flops_hand_reckoned():
    c = _config("spikingformer-8-512")
    # SPS stem, one time step: 224^2 x 27 x 64, 112^2 x 576 x 128,
    # 56^2 x 1152 x 256, 28^2 x 2304 x 512, two operations per MAC
    stem = 2 * (224 ** 2 * 27 * 64 + 112 ** 2 * 576 * 128
                + 56 ** 2 * 1152 * 256 + 28 ** 2 * 2304 * 512)
    assert flops.sps_stem(c) == stem
    assert abs(stem / 1e9 - 5.72) < 0.01
    # one block, one step, L = 196: QKVO 4 x 2 L d^2, MLP 2 x 2 L d ff,
    # QK^T and AV 2 x 2 L^2 d
    block = 8 * 196 * 512 ** 2 + 4 * 196 * 512 * 2048 + 4 * 196 ** 2 * 512
    assert flops.encoder_layer(c, 196, 196) == block
    assert abs(8 * block / 1e9 - 10.50) < 0.01
    assert flops.vision_tokens(c) == 196
    per_image = flops.vision_image(c)
    assert per_image == 4 * (stem + 8 * block) + 2 * 512 * 1000
    assert abs(per_image / 1e9 - 64.9) < 0.05


def test_lm_token_flops_hand_reckoned():
    c = _config("spikingformer-lm")
    # one layer, one step, one token over ctx positions: QKVO 4 x 2 x 256^2,
    # MLP 2 x 2 x 256 x 1024, QK^T and AV 2 x 2 x ctx x 256
    layer = lambda ctx: 8 * 256 ** 2 + 4 * 256 * 1024 + 4 * ctx * 256
    for ctx in (1, 64, 1024):
        assert flops.lm_token(c, ctx) == 4 * 4 * layer(ctx) + 2 * 256 * 32000
    # linear in the context: the serve reader sums it in closed form
    a, b = flops.lm_token(c, 0), flops.lm_token(c, 1) - flops.lm_token(c, 0)
    assert flops.lm_token(c, 777) == a + 777 * b


def test_spike_matmul_counts():
    assert flops.matmul(3, 5, 7) == 210
    # spikes one byte, bf16 weights and outputs two
    assert flops.spike_matmul_bytes(128, 512, 256, 2, 2) == \
        128 * 512 + 512 * 256 * 2 + 128 * 256 * 2


# -- traffic ------------------------------------------------------------------

LAW = {"law": "lognormal", "median": 64, "sigma": 0.5, "min": 16, "max": 128}


def test_law_quantiles_median_and_clipping():
    q = traffic.law_quantiles(LAW, 1001)
    assert q.min() >= 16 and q.max() <= 128
    assert int(np.median(q)) == 64
    assert list(q) == sorted(q)
    wide = dict(LAW, sigma=3.0)
    qw = traffic.law_quantiles(wide, 101)
    assert qw[0] == 16 and qw[-1] == 128       # both tails clipped
    with pytest.raises(ValueError):
        traffic.law_quantiles(dict(LAW, law="pareto"), 10)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = {"requests": 256, "prompt": LAW,
           "output": {"law": "lognormal", "median": 384, "sigma": 0.5,
                      "min": 128, "max": 896}}
    a = traffic.request_sizes(mix, weights.seeded_rng(1, 0))
    b = traffic.request_sizes(mix, weights.seeded_rng(2**31 + 12345, 0))
    again = traffic.request_sizes(mix, weights.seeded_rng(1, 0))
    assert a == again
    assert a != b
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    toks = traffic.prompts(a[:4], 32000, weights.seeded_rng(1, 0))
    assert [len(t) for t in toks] == [p for p, _ in a[:4]]
    assert all(t.dtype == np.int32 and t.max() < 32000 for t in toks)


def test_every_window_of_requests_asks_for_the_same_work():
    """A window serves a run of a few hundred consecutive requests of the
    cycled list: under every seed that run holds as many long prompts and
    as many output tokens, to a few percent (a shuffle varies by a tenth)."""
    mix = json.loads((ROOT / "chip_bench" / "traffic"
                      / "sharegpt_closed256.json").read_text())
    long_prompts, outputs = [], []
    for seed in (1, 2, 2**31 + 5, 2**31 + 99):
        sizes = traffic.request_sizes(mix, weights.seeded_rng(seed, 0))
        for lo in (0, 200, 1900):
            run = [sizes[(lo + i) % len(sizes)] for i in range(330)]
            long_prompts.append(sum(p > 256 for p, _ in run))
            outputs.append(sum(o for _, o in run))
    assert max(long_prompts) - min(long_prompts) <= 3
    assert max(outputs) / min(outputs) < 1.03
    order = traffic.even_order(2048, traffic._STEPS[0], 0.3)
    assert sorted(order.tolist()) == list(range(2048))


@pytest.mark.parametrize("slots,longest,max_chunk", [(3, 16, 48),
                                                      (4, 40, 64)])
def test_warmed_widths_cover_every_wave_the_policy_can_make(
        slots, longest, max_chunk):
    """Every state a closed loop can reach (n slots decoding, the others
    holding up to a whole prompt) asks the server's chunk policy for a
    bite no wider than the runner's widest warmed wave."""
    import types
    from repro.launch.serve import _next_pow2, choose_chunk
    from chip_bench.runners.serve_closed import Runner
    runner = types.SimpleNamespace(
        server=types.SimpleNamespace(fixed_chunk=False,
                                     max_chunk=max_chunk),
        mix={"slots": slots, "prompt": {"max": longest}})
    widest = Runner._widest(runner)
    made = max(_next_pow2(min(choose_chunk(b, n, max_chunk), b, longest))
               for n in range(slots)
               for b in range(1, (slots - n) * longest + 1))
    assert made == _next_pow2(widest)


def test_large_seed_makes_a_key():
    k1, k2 = weights.jax_key(2**31 + 7), weights.jax_key(7)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


# -- percentiles, rates, spreads ---------------------------------------------


def test_percentile_nearest_rank():
    xs = list(range(1, 201))                     # 1..200
    assert stats.percentile(xs, 95) == 190
    assert stats.percentile(xs, 50) == 100
    assert stats.percentile(xs, 100) == 200
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile(list(reversed(xs)), 95) == 190
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rate_and_spread():
    assert stats.rate(300, 2.0) == 150.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


def test_spreads_suggest_bounds(tmp_path):
    from chip_bench import spreads
    for name, vals in (("a", [100, 101, 99, 100, 102, 98]),
                       ("b", [100, 100, 100, 100, 100, 100])):
        (tmp_path / name).write_text("\n".join(json.dumps(
            {"metrics": {"images_per_s": {"value": v, "unit": "images/s"}}})
            for v in vals))
    meds, sp, bound = spreads.summary([tmp_path / "a", tmp_path / "b"])[
        "images_per_s"]
    assert meds == [100, 100]
    assert sp[1] == 0 and sp[0] == pytest.approx(stats.spread(
        [100, 101, 99, 100, 102, 98]))
    assert bound == pytest.approx(max(0.01, 5 * sp[0]))


def test_step_times_names_the_pause():
    from chip_bench.harness import step_times
    ends = [0.1, 0.2, 0.3, 1.3, 1.4, 1.5]
    line = step_times(0.0, ends, n=2)
    assert line.startswith("steps 6: median 100.000 ms")
    assert "longest 1000.000 ms at 1.300 s, 100.000 ms" in line
    assert step_times(0.0, [0.25]).startswith("steps 1: median 250.000")


# -- trace reductions ---------------------------------------------------------


def _data(ops, spans, window=(0, 100)):
    return {"device": [[f"op{i}", "m", s, d, scope]
                       for i, (s, d, scope) in enumerate(ops)],
            "modules": [], "devices": 1,
            "spans": [["bench.window", window[0], window[1] - window[0]]]
            + spans}


def test_union_clip_covered():
    merged = union([(5, 10), (0, 3), (2, 4), (9, 12), (20, 20)])
    assert merged == [(0, 4), (5, 12)]
    assert clip(merged, 3, 6) == [(3, 4), (5, 6)]
    assert covered(merged, 0, 100) == 11


def test_trace_busy_idle_scopes_and_gaps():
    ops = [(0, 15, "jit(f)/while"),                  # holds the next two
           (0, 10, "jit(f)/while/body/dual_engine.fused_layer/dot_general"),
           (10, 5, "jit(f)/while/body/dual_engine.fused_layer/add"),
           (30, 20, "jit(f)/sparse_engine.tile/pallas_call"),
           (90, 20, ""),                                       # past the end
           (-10, 5, "")]                                       # before
    spans = [["bench.step", 0, 60], ["bench.wave", 15, 20],
             ["bench.step", 60, 40]]
    tr = Trace(_data(ops, spans))
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s == pytest.approx(45e-9)    # [0,15) [30,50) [90,100)
    assert tr.idle_share() == pytest.approx(0.55)
    assert tr.scope_s("dual_engine.") == pytest.approx(15e-9)
    assert tr.scope_s("sparse_engine.", "dual_engine.") == \
        pytest.approx(35e-9)
    assert tr.scope_s("binary_engine.") == 0
    gaps = tr.idle_gaps()
    # [50,90) under the second step's span and the first's end; the
    # innermost span covering the midpoint 70 is bench.step (60..100)
    assert gaps[0] == ["bench.step", pytest.approx(40e-9)]
    assert gaps[1] == ["bench.wave", pytest.approx(15e-9)]     # [15,30)
    top = dict(tr.top_ops())
    assert top["dual_engine.fused_layer/dot_general"] == pytest.approx(10e-9)
    assert top["dual_engine.fused_layer/add"] == pytest.approx(5e-9)
    assert top["op0"] == 0                   # self time: its body ran
    assert sum(top.values()) == pytest.approx(tr.busy_s)
    with pytest.raises(ValueError):
        Trace({"device": [], "spans": [], "devices": 1})


def test_hlo_scopes_and_labels():
    text = "\n".join([
        "HloModule jit_prefill_step, entry_computation_layout={...}",
        "  %fusion.3 = bf16[8,196,512]{2,1,0} fusion(%p0), kind=kLoop, "
        "metadata={op_name=\"jit(prefill_step)/jit(main)/"
        "dual_engine.fused_layer/add\" source_file=\"x.py\"}",
        "  ROOT %copy.1 = f32[8]{0} copy(%a), metadata={op_name=\"jit(p)/c\"}",
        "  %p0 = bf16[8]{0} parameter(0)"])
    scopes = hlo_scopes(text)
    assert scopes[("jit_prefill_step", "fusion.3")].endswith(
        "dual_engine.fused_layer/add")
    assert scopes[("jit_prefill_step", "copy.1")] == "jit(p)/c"
    assert ("jit_prefill_step", "p0") not in scopes
    assert op_label("fusion.12", "") == "fusion"
    assert op_label("fusion.3", scopes[("jit_prefill_step", "fusion.3")]) \
        == "dual_engine.fused_layer/add"


# -- BENCHMARK.json -----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_finds_every_file_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        assert (ROOT / conf["reference"]).exists()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        mix = json.loads((ROOT / "chip_bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chip_bench" / "runners"
                / f"{mix['runner']}.py").exists()
        limits = json.loads((ROOT / "chip_bench" / "limits"
                             / f"{w['name']}.json").read_text())["limits"]
        assert all(v > 0 and math.isfinite(v) for v in limits.values())
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert (ROOT / "chip_bench" / "metrics" / f"{m['name']}.py").exists()
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_each_cell_gets_only_its_own_metrics():
    """``workloads`` on a metric keeps it to the cells it names: the
    classification cell reports no serving metric, and the serving cell
    reports exactly its own."""
    from chip_bench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = lambda ms: {m["name"] for m in ms}
    vision = harness.Cell(bench, "sf8-512.classify", 1)
    serve = harness.Cell(bench, "sflm.batch", 1)
    assert names(vision.end_to_end) == {"images_per_s", "setup_s"}
    assert not any(n.endswith(".batch") for n in names(vision.per_layer))
    assert names(serve.end_to_end) == {"tokens_per_s", "setup_s"}
    assert names(serve.per_layer) == {"idle_share.batch", "mfu.batch",
                                      "wave_device_ms.batch",
                                      "wave_host_ms.batch"}
    assert serve.config["registry"] == "spikingformer-lm"
    assert serve.traffic["runner"] == "serve_closed" and serve.chips == 1
