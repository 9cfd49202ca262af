"""CPU rehearsal of the closed-loop serving cell at SMOKE size: the runner
drives ``BatchedServer`` end to end with the look for a chip left out,
no program compiles inside the window, the check fails when the served
tokens are altered where they are produced, the control reads above the
program, and the reference agrees with the server's own logits."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import control, harness, run, weights
from chip_bench.reference import spiking_lm as ref
from chip_bench_smoke import SmokeCell

CELL = "sflm.batch"


def test_rehearsal_is_correct_and_counts():
    out = run.run_cell(SmokeCell(CELL), jax.devices()[:1], 1.5, False)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["served_token_gap"][0] == 0.0
    assert out["checks"]["served_token_gap_mean"][0] == 0.0


def test_window_compiles_nothing_and_closed_loop_holds():
    cell = SmokeCell(CELL, seed=4)
    runner = harness.load_module(
        harness.HERE / "runners" / "serve_closed.py", "serve_runner").Runner(
        cell, jax.devices()[:1])
    runner.setup()
    counter = harness.CompileCounter()
    harness.measure(runner, 1.0, False, counter)
    assert counter.names == []
    # every client always has one request in flight
    assert len(runner.live) == cell.traffic["clients"]
    n = runner.counters()
    assert n["tokens"] > 0 and n["processed"] > n["tokens"]
    for req, sub, times in runner.finished:
        assert len(times) == len(req.generated) == req.max_new_tokens
        assert times[0] >= sub and times == sorted(times)


def test_scopes_join_every_warmed_width():
    runner = harness.load_module(
        harness.HERE / "runners" / "serve_closed.py", "serve_runner").Runner(
        SmokeCell(CELL, seed=3), jax.devices()[:1])
    runner.setup()
    scopes = runner.scopes()
    assert len(runner.widths) > 1 and scopes
    assert {m for m, _ in scopes} == {"jit_serve_step"}
    # the layers run in one scanned loop of the step
    assert any(v.startswith("jit(serve_step)/while/")
               for v in scopes.values())


def test_altered_token_fails_the_check(monkeypatch):
    """A fault where the token is produced: the serve step's logits are
    rolled by one along the vocabulary, so every greedy token is the
    neighbour of the right one."""
    from repro.launch import steps
    real = steps.build_batched_serve_step

    def faulty(cfg):
        step = real(cfg)

        def shifted(*args):
            logits, cache = step(*args)
            return jnp.roll(logits, 1, axis=-1), cache
        return shifted
    monkeypatch.setattr(steps, "build_batched_serve_step", faulty)
    out = run.run_cell(SmokeCell(CELL), jax.devices()[:1], 1.0, False)
    assert out["correct"] is False
    assert out["checks"]["served_token_gap"][0] > \
        out["checks"]["served_token_gap"][1]


def test_control_reads_above_the_program():
    got = control.readings(SmokeCell(CELL, seed=8), jax.devices()[:1], 1.0,
                           ["int8", "float8_e4m3fn"])
    for ctl in ("int8", "float8_e4m3fn"):
        assert got[ctl]["served_token_gap"] > \
            got["honest"]["served_token_gap"]


def test_reference_matches_served_logits():
    """Chunked prefill and decode through the packed cache against the
    reference's whole-sequence forward, in float32."""
    from repro.launch.serve import BatchedServer, Request
    from chip_bench.runners.common import program_config, seeded_params
    cell = SmokeCell(CELL, seed=6)
    c = cell.config
    cfg = program_config(c)
    params = seeded_params(c, cfg, 6)
    srv = BatchedServer(cfg, params, 2, 48, trace_logits=True)
    rng = weights.seeded_rng(6, 0)
    for i, n in enumerate((21, 5)):
        srv.submit(Request(i, rng.integers(0, c["vocab_size"], n,
                                           dtype=np.int32), 7))
    srv.run()
    assert len(srv.completed) == 2
    for r in srv.completed:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        want, _ = ref.forward(c, params, jnp.asarray(seq))
        want = np.asarray(want)[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(r.logit_trace), want,
                                   atol=1e-4, rtol=0)
        assert list(want.argmax(-1)) == r.generated

