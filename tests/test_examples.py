"""Smoke-run every ``examples/`` script end to end.

Nothing else in the suite imports the examples, so they rot silently —
these tests execute each one in a subprocess (fresh interpreter, the
exact invocation the README advertises) in smoke mode and assert a clean
exit plus a recognizable line of output. Budget-heavy scripts are marked
``slow`` (PR CI skips them; pushes to main run the full tier).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, *args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"{script} failed:\n--- stdout ---\n{proc.stdout[-2000:]}\n" \
        f"--- stderr ---\n{proc.stderr[-2000:]}"
    return proc.stdout


def test_serve_lm_smoke():
    out = run_example("serve_lm.py", "--arch", "spikingformer-lm",
                      "--smoke", "--requests", "2", "--slots", "2",
                      "--prompt-len", "5", "--max-new", "2",
                      "--max-len", "32")
    assert "kv cache" in out and "requests" in out


def test_serve_lm_quantized_smoke():
    out = run_example("serve_lm.py", "--arch", "spikingformer-lm",
                      "--smoke", "--requests", "2", "--slots", "2",
                      "--prompt-len", "5", "--max-new", "2",
                      "--max-len", "32", "--quantize", "int8")
    assert "weights" in out and "int8" in out


@pytest.mark.slow
def test_quickstart_smoke():
    out = run_example("quickstart.py", "--steps", "3")
    assert "layer spike sparsity" in out


@pytest.mark.slow
def test_train_spikingformer_smoke():
    out = run_example("train_spikingformer.py", "--steps", "3",
                      "--batch", "4")
    assert "loss:" in out


@pytest.mark.slow
def test_dual_engine_walkthrough():
    out = run_example("dual_engine_walkthrough.py")
    assert "bitwise: True" in out
