"""The per-scope readers on a trace recorded on a TPU v5e with every phase
of the vision eval step named: four eval steps of spikingformer-8-512 at
batch 64, reduced to the compact form with the op_name scopes of the
program joined. The expected numbers are the reduction's own readings of
this file; they are not benchmark results."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from chip_bench import harness, trace

FIX = pathlib.Path(__file__).resolve().parents[2] / "chip_bench" / "fixtures"
IMAGES = 4 * 64
READINGS = {                     # reader -> device seconds of its scopes
    "stem_ms_per_image.vision": 0.299272787,
    "lif_ms_per_image.vision": 0.243962807,
    "sparse_ms_per_image.vision": 0.056496679,
    "binary_ms_per_image.vision": 0.022431795,
    "engine_ms_per_image.vision": 0.152122874,
}


@pytest.fixture(scope="module")
def scoped():
    with gzip.open(FIX / "classify_4steps_scoped.trace.json.gz", "rt") as f:
        return json.load(f)


class _Cell:
    config = None


def _read(name, data, images):
    reading = harness.Reading(trace.Trace(data), {"images": images},
                              _Cell(), {})
    mod = harness.load_module(FIX.parent / "metrics" / f"{name}.py", name)
    return mod.read(reading)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_scope_readers(scoped, name):
    assert _read(name, scoped, IMAGES) == pytest.approx(
        1e3 * READINGS[name] / IMAGES)
    assert _read(name, scoped, 0) is None


@pytest.mark.parametrize("name", ["stem_ms_per_image.vision",
                                  "lif_ms_per_image.vision",
                                  "sparse_ms_per_image.vision",
                                  "binary_ms_per_image.vision"])
def test_scope_readers_silent_without_scopes(scoped, name):
    """A program without the scopes (the parent of this reader) reads
    nothing rather than 0."""
    bare = dict(scoped, device=[op[:4] + [""] for op in scoped["device"]])
    assert _read(name, bare, IMAGES) is None


def test_engines_within_layer_program(scoped):
    tr = trace.Trace(scoped)
    engine = tr.scope_s("sparse_engine.", "binary_engine.", "dual_engine.")
    assert tr.scope_s("sparse_engine.") + tr.scope_s("binary_engine.") \
        <= engine
    assert engine == pytest.approx(tr.scope_s("dual_engine."))


def test_phases_cover_busy_time(scoped):
    tr = trace.Trace(scoped)
    phases = tr.scope_s("sps.stem", "spikingformer.blocks",
                        "spikingformer.head")
    assert phases >= 0.95 * tr.busy_s
    # stem, blocks and head do not overlap: their sum is their union
    assert tr.scope_s("sps.stem") + tr.scope_s("spikingformer.blocks") \
        + tr.scope_s("spikingformer.head") == pytest.approx(phases)
