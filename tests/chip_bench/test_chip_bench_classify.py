"""CPU rehearsal of the classification cell at SMOKE size: the runner's
set-up, window and check run end to end with the look for a chip left
out, the check fails when the timed path alters an answer, the control
reads above the program, and the reference agrees with the program."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import control, run, weights
from chip_bench.reference import spikingformer as ref
from chip_bench_smoke import SmokeCell

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "sf8-512.classify"


def test_rehearsal_is_correct_and_counts():
    out = run.run_cell(SmokeCell(CELL), jax.devices()[:1], 1.0, False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert all(v == 0.0 for v, _ in out["checks"].values())


def test_altered_answer_fails_the_check(monkeypatch):
    """A fault where the answer is produced: the eval step negates the
    logits of the first image of every batch."""
    from repro.launch import steps
    real = steps.build_prefill_step

    def faulty(cfg):
        step = real(cfg)
        return lambda params, batch: step(params, batch).at[0].multiply(-1)
    monkeypatch.setattr(steps, "build_prefill_step", faulty)
    out = run.run_cell(SmokeCell(CELL), jax.devices()[:1], 0.5, False)
    assert out["correct"] is False
    assert any(v > lim for v, lim in out["checks"].values())


def test_control_reads_above_the_program():
    got = control.readings(SmokeCell(CELL, seed=9), jax.devices()[:1], 0.5,
                           ["int8", "float8_e4m3fn"])
    for ctl in ("int8", "float8_e4m3fn"):
        assert got[ctl]["top1_gap"] >= got["honest"]["top1_gap"]
        assert got[ctl]["logit_rel_rms"] > got["honest"]["logit_rel_rms"]


def test_reference_matches_program_in_float32():
    from repro.launch import steps
    from repro.models import registry
    cell = SmokeCell(CELL, seed=3)
    from chip_bench.runners.common import program_config
    cfg = program_config(cell.config)
    params = weights.make_params(cell.config, 3)
    weights.check_layout(params, jax.eval_shape(
        lambda: registry.init(cfg, jax.random.PRNGKey(0))))
    imgs = weights.images(cell.config, 3, 4)
    prog = jax.jit(steps.build_prefill_step(cfg))(params, {"images": imgs})
    want, dens = ref.forward(cell.config, params, imgs)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert 0.05 < float(jnp.min(dens)) and float(jnp.max(dens)) < 0.7


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload", CELL, "--seed",
         "2147483700", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", "chip_bench/run.py"]
