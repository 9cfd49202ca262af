"""The trace reduction and the per-layer readers on traces recorded on a
TPU v5e: three eval steps of spikingformer-8-512 at batch 64 (the raw
``.xplane.pb`` and the op_name scopes of its program), and twenty waves of
the spikingformer-lm server (reduced to the compact form). The expected
numbers are the reduction's own readings of these files, checked by hand
against the trace's module events; they are not benchmark results."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from chip_bench import flops, harness, trace

FIX = pathlib.Path(__file__).resolve().parents[2] / "chip_bench" / "fixtures"
PEAKS = {"bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def classify():
    with gzip.open(FIX / "classify_3steps.scopes.json.gz", "rt") as f:
        scopes = {(m, op): sc for m, op, sc in json.load(f)}
    return trace.load(str(FIX / "classify_3steps.xplane.pb"), scopes)


@pytest.fixture(scope="module")
def batch():
    with gzip.open(FIX / "batch_20waves.trace.json.gz", "rt") as f:
        return json.load(f)


class _Cell:
    def __init__(self, config):
        self.config = json.loads((FIX.parent / "configs"
                                  / f"{config}.json").read_text())


def _read(name, data, counts, config):
    reading = harness.Reading(trace.Trace(data), counts, _Cell(config), PEAKS)
    mod = harness.load_module(FIX.parent / "metrics" / f"{name}.py", name)
    return mod.read(reading)


def test_load_joins_modules_and_scopes(classify):
    assert classify["devices"] == 1
    assert [m[0] for m in classify["modules"]] == ["jit_prefill_step"] * 3
    assert len(classify["device"]) == 3384
    assert all(op[1] == "jit_prefill_step" for op in classify["device"])
    scoped = [op for op in classify["device"] if "dual_engine." in op[4]]
    assert scoped and all(op[0] for op in scoped)
    assert {s[0] for s in classify["spans"]} == {"bench.window",
                                                 "bench.step"}


def test_classify_busy_scope_and_self_time(classify):
    tr = trace.Trace(classify)
    assert tr.window_s == pytest.approx(0.360010323)
    assert tr.busy_s == pytest.approx(0.35779641)
    assert tr.idle_share() < 0.01          # three programs back to back
    # the eight blocks run under dual_engine.fused_layer; the stem does not
    assert tr.scope_s("dual_engine.") == pytest.approx(0.114084443)
    assert tr.scope_s("sparse_engine.", "binary_engine.") == 0
    top = tr.top_ops(n=10 ** 6)
    assert sum(s for _, s in top) == pytest.approx(tr.busy_s, rel=1e-6)
    assert len(tr.top_ops()) == 10
    gaps = tr.idle_gaps()
    assert sum(g for _, g in gaps) <= tr.window_s - tr.busy_s + 1e-12


def test_vision_readers(classify):
    images = 3 * 64
    assert _read("idle_share.vision", classify, {"images": images},
                 "spikingformer-8-512") == pytest.approx(
                     100 * (1 - 0.35779641 / 0.360010323))
    per_image = flops.vision_image(json.loads(
        (FIX.parent / "configs" / "spikingformer-8-512.json").read_text()))
    assert _read("mfu.vision", classify, {"images": images},
                 "spikingformer-8-512") == pytest.approx(
                     100 * images * per_image / 0.360010323 / 197e12)
    assert _read("engine_ms_per_image.vision", classify, {"images": images},
                 "spikingformer-8-512") == pytest.approx(
                     1e3 * 0.114084443 / images)
    # nothing to read: no images finished in the window
    assert _read("mfu.vision", classify, {"images": 0},
                 "spikingformer-8-512") is None


def test_serve_readers(batch):
    tr = trace.Trace(batch)
    assert [s[0] for s in tr.spans].count("bench.wave") == 20
    assert [m[0] for m in batch["modules"]].count("jit_serve_step") == 20
    counts = {"waves": 20, "processed": 700, "context": 700 * 300}
    c = "spikingformer-lm"
    assert _read("wave_device_ms.batch", batch, counts, c) == \
        pytest.approx(1e3 * tr.busy_s / 20)
    spans = sum(s[2] for s in tr.spans if s[0] == "bench.wave") * 1e-9
    assert _read("wave_host_ms.batch", batch, counts, c) == \
        pytest.approx(1e3 * (spans - tr.busy_s) / 20)
    host = _read("wave_host_ms.batch", batch, counts, c)
    dev = _read("wave_device_ms.batch", batch, counts, c)
    # the waves fill the window but for the harness's own bookkeeping
    assert 0.9 * tr.window_s < (host + dev) * 20e-3 <= tr.window_s
    assert 0 < _read("idle_share.batch", batch, counts, c) < 100
    cfg = _Cell(c).config
    ops = 700 * flops.lm_token(cfg, 0) + 700 * 300 * (
        flops.lm_token(cfg, 1) - flops.lm_token(cfg, 0))
    assert _read("mfu.batch", batch, counts, c) == pytest.approx(
        100 * ops / tr.window_s / 197e12)
    assert _read("wave_device_ms.batch", batch, {"waves": 0}, c) is None
    empty = dict(batch, spans=[x for x in batch["spans"]
                               if x[0] != "bench.wave"])
    assert _read("wave_host_ms.batch", empty, counts, c) is None
