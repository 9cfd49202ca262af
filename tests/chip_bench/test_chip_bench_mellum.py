"""CPU rehearsal of the cell ``mellum2.chat`` at SMOKE size: the runner
``serve_closed_lm`` drives ``BatchedServer`` end to end with the look for
a chip left out, the window compiles nothing, a token altered where it is
produced reads not correct, the float8 control reads above the program,
and the reference agrees with the program (prefill, and serving through
the window and full rings). The counts and readers of the cell's
per-layer metrics are checked on hand-made traces."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench import control, flops_moe, harness, run, weights
from chip_bench.reference import mellum2 as ref
from chip_bench.runners import serve_closed_lm as lm

CELL = "mellum2.chat"
# the registry's SMOKE shape: 8 layers in two periods, window 8, 16
# experts with 4 held, in float32
SMOKE = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
         "num_hidden_layers": 8, "sliding_window": 8,
         "moe_intermediate_size": 32, "num_experts": 4, "dtype": "float32",
         "smoke": True}
TRAFFIC = {"runner": "serve_closed_lm", "clients": 3, "slots": 3,
           "max_len": 48, "chunk": 4, "requests": 16, "lead_in_s": 0.5,
           "prompt": {"law": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 3, "max": 24},
           "output": {"law": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
           "check": 3}


class SmokeCell(harness.Cell):
    """``mellum2.chat`` with its model cut to SMOKE and its traffic to a
    few requests."""

    def __init__(self, seed: int = 5):
        super().__init__(harness.load_json("BENCHMARK.json"), CELL, seed)
        c = self.config
        c.update(SMOKE)
        c["layer_types"] = c["layer_types"][:8]
        c["mlp_layer_types"] = c["mlp_layer_types"][:8]
        c["deployment"] = dict(c["deployment"], published_num_experts=16)
        self.traffic = copy.deepcopy(TRAFFIC)
        self.chips = 1


def _runner(cell, name="mellum_runner"):
    return harness.load_module(
        harness.HERE / "runners" / "serve_closed_lm.py", name).Runner(
        cell, jax.devices()[:1])


def _params(seed):
    c = SmokeCell(seed).config
    cfg = lm.program_config(c)
    return c, cfg, lm.seeded_params(c, cfg, seed)


def test_rehearsal_is_correct_and_counts():
    out = run.run_cell(SmokeCell(), jax.devices()[:1], 1.5, False)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    # float32 program against the float32 reference
    assert out["checks"]["served_token_gap_mean"][0] < 1e-4


def test_window_compiles_nothing_and_counts_window_context():
    cell = SmokeCell(seed=4)
    runner = _runner(cell)
    runner.setup()
    counter = harness.CompileCounter()
    harness.measure(runner, 1.0, False, counter)
    assert counter.names == []
    assert len(runner.live) == cell.traffic["clients"]
    n = runner.counters()
    assert n["tokens"] > 0 and n["processed"] > n["tokens"]
    # a sliding layer attends at most the window; prompts pass it
    assert n["processed"] < n["context_window"] < n["context"]
    assert n["context_window"] <= 8 * n["processed"]


def test_altered_token_fails_the_check(monkeypatch):
    """The serve step's logits rolled by one along the vocabulary: every
    greedy token is the neighbour of the right one."""
    from repro.launch import steps
    real = steps.build_batched_serve_step

    def faulty(cfg):
        step = real(cfg)

        def shifted(*args):
            logits, cache = step(*args)
            return jnp.roll(logits, 1, axis=-1), cache
        return shifted
    monkeypatch.setattr(steps, "build_batched_serve_step", faulty)
    out = run.run_cell(SmokeCell(), jax.devices()[:1], 1.0, False)
    assert out["correct"] is False
    assert out["checks"]["served_token_gap_mean"][0] > \
        out["checks"]["served_token_gap_mean"][1]


def test_control_reads_above_the_program():
    got = control.readings(SmokeCell(seed=8), jax.devices()[:1], 1.0,
                           ["float8_e4m3fn"])
    assert got["float8_e4m3fn"]["served_token_gap_mean"] > \
        got["honest"]["served_token_gap_mean"]
    assert got["float8_e4m3fn"]["served_token_mismatch"] > 0


def test_program_config_refuses_a_differing_file():
    c = SmokeCell().config
    c["rope_parameters"] = copy.deepcopy(c["rope_parameters"])
    c["rope_parameters"]["full_attention"]["factor"] = 8
    with pytest.raises(ValueError, match="rope_parameters"):
        lm.program_config(c)


def test_prefill_logits_match_the_reference():
    """The program's whole-sequence forward against the reference, float32
    both: the same arithmetic up to summation order (observed 5.4e-6 on
    logits up to 3.9); 5e-5 leaves room for XLA's CPU blocking, and
    bfloat16 anywhere would miss it by two orders."""
    from repro.models import registry
    c, cfg, params = _params(6)
    toks = jnp.asarray(np.random.default_rng(6).integers(0, 256, 40),
                       jnp.int32)
    got, _ = registry.forward(params, cfg, {"tokens": toks[None]})
    want = ref.forward(c, params, toks)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=5e-5, rtol=0)


def test_served_logits_match_the_reference():
    """Bites, then decode through the window rings (8 + 3 positions) and
    the full cache, 40 positions past the window: every served position's
    logits against the reference's full forward. float32 on both sides
    (observed 4.1e-6); tolerance as in the prefill test."""
    from repro.launch.serve import BatchedServer, Request
    c, cfg, params = _params(7)
    srv = BatchedServer(cfg, params, 2, 48, chunk=4, trace_logits=True)
    rng = weights.seeded_rng(7, 0)
    for i, (n, new) in enumerate(((13, 27), (5, 9))):
        srv.submit(Request(i, rng.integers(0, 256, n, dtype=np.int32), new))
    srv.run()
    assert len(srv.completed) == 2
    for r in srv.completed:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        want = np.asarray(ref.forward(c, params, jnp.asarray(seq)))
        want = want[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(r.logit_trace), want,
                                   atol=5e-5, rtol=0)
        assert list(want.argmax(-1)) == r.generated


def test_reference_yarn_is_the_program_yarn():
    c, cfg, _ = _params(3)
    from repro.models import nn
    f, scale = ref.rope_freqs(c, "full_attention")
    np.testing.assert_allclose(nn.yarn_freqs(16, cfg.rope_theta, cfg.yarn),
                               f, rtol=1e-6)
    assert scale == cfg.yarn.attention_factor


def test_flops_hand_reckoned():
    c = harness.load_json("chip_bench/configs/mellum2-12b-a2.5b.json")
    d, f, qd, kvd = 2304, 896, 4096, 512
    pair = 3 * 2 * d * f
    assert flops_moe.expert_pair(c) == pair
    layer = 2 * (2 * d * qd + 2 * d * kvd) + 2 * d * 64 + 1.0 * pair
    assert flops_moe.token(c) == 28 * layer + 2 * d * 98304
    assert flops_moe.layers_of(c, "full_attention") == 7
    assert flops_moe.attend(c, 10) == 4 * 10 * qd
    # 8 held experts' 3 matrices once a wave and layer, 1 expected pair a
    # row, its rows in and out of each matmul, bf16
    assert flops_moe.gmm_bytes(c, 100, 2) == 28 * (
        2 * 8 * 3 * d * f * 2 + 100 * 3 * (d + f) * 2)
    assert flops_moe.gmm_ops(c, 100) == 28 * 100 * pair


class _Trace:
    def __init__(self, scoped, window_s=2.0):
        self.scoped, self.window_s, self.devices = scoped, window_s, 1

    def scope_s(self, *prefixes):
        return sum(v for k, v in self.scoped.items()
                   if k.startswith(prefixes))


@pytest.mark.parametrize("metric,scoped,want", [
    ("moe_ms_per_wave.mellum", {"moe.experts": 0.3, "moe.router": 0.1},
     2.0),
    ("attn_window_ms_per_wave.mellum", {"attn.window": 0.5}, 2.5),
    ("attn_full_ms_per_wave.mellum", {"attn.full": 0.7}, 3.5),
])
def test_scope_readers(metric, scoped, want):
    mod = harness.load_module(harness.HERE / "metrics" / f"{metric}.py",
                              "reader")
    cell = harness.Cell(harness.load_json("BENCHMARK.json"), CELL, 1)
    counts = {"waves": 200, "processed": 4000}
    r = harness.Reading(_Trace(scoped), counts, cell, {})
    assert mod.read(r) == pytest.approx(want)
    assert mod.read(harness.Reading(_Trace({}), counts, cell, {})) is None


def test_roofline_and_mfu_readers():
    cell = harness.Cell(harness.load_json("BENCHMARK.json"), CELL, 1)
    c = cell.config
    peaks = harness.peaks_for("TPU v5 lite")
    counts = {"waves": 100, "processed": 40000, "context": 4 * 10 ** 7,
              "context_window": 2 * 10 ** 7}
    gmm = harness.load_module(
        harness.HERE / "metrics" / "gmm_roofline_share.mellum.py", "gmm")
    r = harness.Reading(_Trace({"moe.experts": 1.0}), counts, cell, peaks)
    least = flops_moe.gmm_bytes(c, 40000, 100) / peaks["hbm_bytes_per_s"]
    assert least > flops_moe.gmm_ops(c, 40000) / peaks["bf16_flops_per_s"]
    assert gmm.read(r) == pytest.approx(100 * least)
    mfu = harness.load_module(harness.HERE / "metrics" / "mfu.mellum.py",
                              "mfu")
    ops = 40000 * flops_moe.token(c) + 7 * flops_moe.attend(c, 4 * 10 ** 7) \
        + 21 * flops_moe.attend(c, 2 * 10 ** 7)
    assert mfu.read(r) == pytest.approx(100 * ops / 2.0
                                        / peaks["bf16_flops_per_s"])
