"""The ``lif_kernel_share.vision`` reader on a trace recorded on a TPU v5e:
one jitted program with a LIF on the one-pass kernel
(``lif.scan/lif.kernel``, bf16 (4, 8, 196, 512)) and one on the scan (a
bare ``lif.scan``, bf16 (4, 8, 1, 256); it was recorded under an earlier
rule that left inputs of that size to the scan), run three times inside
a ``bench.window`` span. The expected numbers are the reduction's own
readings of this file; they are not benchmark results."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from chip_bench import harness, trace

FIX = pathlib.Path(__file__).resolve().parents[2] / "chip_bench" / "fixtures"
NAME = "lif_kernel_share.vision"
LIF_S = 5.0666e-05            # device seconds under lif.
KERNEL_S = 4.278e-05          # of which under lif.kernel


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIX / "lif_one_pass.trace.json.gz", "rt") as f:
        return json.load(f)


class _Cell:
    config = None


def _read(data):
    reading = harness.Reading(trace.Trace(data), {"images": 0}, _Cell(), {})
    return harness.load_module(FIX.parent / "metrics" / f"{NAME}.py",
                               NAME).read(reading)


def _parts(op):
    return op[4].split("/")


def test_fixture_holds_both_forms(recorded):
    ops = trace.Trace(recorded).ops
    assert any("lif.kernel" in _parts(o) for o in ops)
    assert any("lif.scan" in _parts(o) and "lif.kernel" not in _parts(o)
               for o in ops)


def test_lif_kernel_share_reader(recorded):
    tr = trace.Trace(recorded)
    assert tr.scope_s("lif.") == pytest.approx(LIF_S)
    assert tr.scope_s("lif.kernel") == pytest.approx(KERNEL_S)
    assert _read(recorded) == pytest.approx(100 * KERNEL_S / LIF_S)


@pytest.mark.parametrize("drop", ["lif.kernel", "lif.scan"])
def test_lif_kernel_share_reads_the_kernel_scope(recorded, drop):
    """Without the one-pass scope (the parent's program) the reader reads
    nothing; without the scan's bare ops, all of the LIF time."""
    if drop == "lif.kernel":
        device = [o[:4] + ["/".join(p for p in _parts(o) if p != drop)]
                  for o in recorded["device"]]
    else:
        device = [o for o in recorded["device"]
                  if "lif.scan" not in _parts(o) or "lif.kernel" in _parts(o)]
    got = _read(dict(recorded, device=device))
    assert got is None if drop == "lif.kernel" else got == pytest.approx(100)
