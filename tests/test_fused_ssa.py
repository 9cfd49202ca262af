"""Fused dual-engine SSA step (kernels/fused_ssa.py, engine.overlap).

Pins, in order of the stack:
  * the fused kernel is bitwise equal to the sequential oracle
    (``reference_bundle``) for both projection-epilogue families
    (BN — vision, RoPE — token/causal), including non-divisible L,
    all-zero spike rows, fully dark time slabs (the occupancy skip),
    and int8-quantized weights;
  * the executed-step counts output is exact: full-occupancy inputs
    count every sub-step, dark slabs are skipped and *not* counted;
  * ``resolve_overlap`` dispatch rules mirror ``resolve_sparse_path``:
    off by default, explicit honored (also under jit), auto fuses only
    on concrete inputs whose bundle flops clear ``min_flops``, tracer ->
    off;
  * whole-model logits are bitwise equal between ``overlap='off'`` and
    ``overlap='fused'`` on all three spikingformer configs, and whole-
    model gradients match bitwise (the custom VJP recomputes the
    sequential composition);
  * profiler annotations (``engine.annotate``) are metadata-only:
    annotated and unannotated runs are bitwise identical;
  * the per-head schedule extension keeps the scalar path numerically
    unchanged, and ``fused_step_metrics`` derives the measured hidden
    fraction from the kernel's counts.

Bit-exactness strategy matches tests/test_spike_decode.py: dyadic-grid
weights make fp32 accumulation order-exact, so equality is to the bit.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container ships the fixed-seed shim
    from _propcheck import given, settings, strategies as st

from repro.configs import get_config
from repro.core import dual_engine as de
from repro.core import engine as E
from repro.core.spiking import SpikingConfig
from repro.kernels.fused_ssa import fused_ssa, reference_bundle
from repro.models import registry


def _dyadic(key, shape):
    return (jax.random.randint(key, shape, -128, 128)
            .astype(jnp.float32)) * (2.0 ** -8)


def _spikes(key, shape, density=0.3):
    return (jax.random.uniform(key, shape) < density).astype(jnp.float32)


def _bn_aux(key, q_dim):
    k1, k2 = jax.random.split(key)
    mean = _dyadic(k1, (3, q_dim)) * 0.25
    var = jnp.abs(_dyadic(k2, (3, q_dim))) + 0.5
    scale = jnp.ones((3, q_dim)) * 1.25
    bias = jnp.full((3, q_dim), 0.0625)
    return jnp.stack([mean, var, scale, bias], axis=1)


def _rope_aux(seq, head_dim, theta=10000.0):
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    return jnp.stack([jnp.cos(ang), jnp.sin(ang)])


def _bundle(key, t, b, l, k, heads, hd, *, family, quant=False,
            dark_slab=False):
    ks = jax.random.split(key, 3)
    x = _spikes(ks[0], (t, b, l, k))
    x = x.at[:, :, min(2, l - 1)].set(0.0)          # an all-zero row
    if dark_slab:
        x = x.at[0, 0].set(0.0)                     # whole slab dark
    if quant:
        w3 = jax.random.randint(ks[1], (3, k, heads * hd), -128, 128
                                ).astype(jnp.int8).astype(jnp.float32)
        scale3 = jnp.abs(_dyadic(ks[2], (3, heads * hd))) + 0.5
    else:
        w3 = _dyadic(ks[1], (3, k, heads * hd))
        scale3 = None
    aux = _bn_aux(ks[2], heads * hd) if family == "bn" \
        else _rope_aux(l, hd)
    return x, w3, scale3, aux


SHAPES = [(2, 2, 13, 24, 4, 8),    # non-divisible L
          (2, 1, 16, 32, 2, 16),
          (3, 2, 9, 17, 3, 6)]     # odd everything (even head_dim)


@pytest.mark.parametrize("family", ["bn", "rope"])
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_kernel_matches_oracle_bitwise(family, shape):
    t, b, l, k, heads, hd = shape
    scfg = SpikingConfig(time_steps=t)
    x, w3, scale3, aux = _bundle(jax.random.PRNGKey(hash(shape) % 997),
                                 t, b, l, k, heads, hd, family=family,
                                 dark_slab=True)
    kw = dict(family=family, num_heads=heads, head_dim=hd,
              scale=1.0 / math.sqrt(hd), causal=(family == "rope"))
    out, cnt = fused_ssa(x, w3, scale3, aux, 0.3, **kw)
    ref = reference_bundle(x, w3, scale3, aux, 0.3, scfg, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    cnt = np.asarray(cnt)
    # dark (t=0, b=0) slab is skipped: t*b - 1 executed per projection
    np.testing.assert_array_equal(cnt[:, :3], t * b - 1)
    np.testing.assert_array_equal(cnt[:, 3], 2 * t * b)


def test_fused_kernel_int8_weights_bitwise():
    t, b, l, k, heads, hd = 2, 2, 13, 24, 4, 8
    scfg = SpikingConfig(time_steps=t)
    x, w3, scale3, aux = _bundle(jax.random.PRNGKey(7), t, b, l, k,
                                 heads, hd, family="bn", quant=True)
    kw = dict(family="bn", num_heads=heads, head_dim=hd,
              scale=1.0 / math.sqrt(hd))
    out, _ = fused_ssa(x, w3, scale3, aux, 0.3, **kw)
    ref = reference_bundle(x, w3, scale3, aux, 0.3, scfg, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_kernel_all_zero_input():
    t, b, l, k, heads, hd = 2, 1, 8, 16, 2, 8
    scfg = SpikingConfig(time_steps=t)
    x = jnp.zeros((t, b, l, k))
    w3 = _dyadic(jax.random.PRNGKey(3), (3, k, heads * hd))
    aux = _bn_aux(jax.random.PRNGKey(4), heads * hd)
    kw = dict(family="bn", num_heads=heads, head_dim=hd,
              scale=1.0 / math.sqrt(hd))
    out, cnt = fused_ssa(x, w3, None, aux, 0.3, **kw)
    ref = reference_bundle(x, w3, None, aux, 0.3, scfg, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # every projection slab dark -> zero executed projection sub-steps
    np.testing.assert_array_equal(np.asarray(cnt)[:, :3], 0)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6), st.floats(0.05, 0.6))
def test_fused_kernel_property_random_density(seed, density):
    t, b, l, k, heads, hd = 2, 2, 11, 20, 2, 8
    scfg = SpikingConfig(time_steps=t)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = _spikes(ks[0], (t, b, l, k), density)
    w3 = _dyadic(ks[1], (3, k, heads * hd))
    aux = _bn_aux(ks[2], heads * hd)
    kw = dict(family="bn", num_heads=heads, head_dim=hd,
              scale=1.0 / math.sqrt(hd))
    out, _ = fused_ssa(x, w3, None, aux, 0.3, **kw)
    ref = reference_bundle(x, w3, None, aux, 0.3, scfg, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# dispatch rules
# ---------------------------------------------------------------------------


BIG = 1 << 23


def test_resolve_overlap_modes():
    x = jnp.ones((4, 4))
    assert E.resolve_overlap(None, x, BIG) == "off"
    off = E.EngineConfig(overlap="off")
    fused = E.EngineConfig(overlap="fused")
    auto = E.EngineConfig(overlap="auto")
    assert E.resolve_overlap(off, x, BIG) == "off"
    assert E.resolve_overlap(fused, x, 0) == "fused"
    assert E.resolve_overlap(auto, x, BIG) == "fused"
    assert E.resolve_overlap(auto, x, 10) == "off"      # below min_flops
    assert E.resolve_overlap(auto, None, BIG) == "off"  # no concrete input

    seen = []

    @jax.jit
    def f(u):
        seen.append((E.resolve_overlap(auto, u, BIG),
                     E.resolve_overlap(fused, u, 0)))
        return u

    f(x)
    assert seen == [("off", "fused")]  # tracer -> off; explicit honored


def test_engine_config_rejects_bad_overlap():
    with pytest.raises(ValueError):
        E.EngineConfig(overlap="pipelined")


# ---------------------------------------------------------------------------
# whole-model parity (logits + grads) and annotation bitwise-neutrality
# ---------------------------------------------------------------------------


SPIKING_ARCHS = ["spikingformer-4-256", "spikingformer-8-512",
                 "spikingformer-lm"]


def _model_setup(arch):
    cfg = get_config(arch, smoke=True)
    params = jax.tree_util.tree_map(
        lambda a: jnp.round(a * 256) / 256
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        registry.init(cfg, jax.random.PRNGKey(0)))
    if cfg.family == "dense":
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (2, 16), 0, cfg.vocab_size)}
    else:
        batch = {"images": jax.random.uniform(
            jax.random.PRNGKey(1),
            (2, cfg.vision.img_size, cfg.vision.img_size,
             cfg.vision.in_channels))}
    return cfg, params, batch


@pytest.mark.parametrize("arch", SPIKING_ARCHS)
def test_model_logits_bitwise_fused_vs_off(arch):
    cfg, params, batch = _model_setup(arch)
    outs = {}
    for ov in ("off", "fused"):
        with E.use_engine(cfg.engine.replace(overlap=ov)):
            logits, _ = registry.forward(params, cfg, batch)
        outs[ov] = np.asarray(logits)
    np.testing.assert_array_equal(outs["off"], outs["fused"])


@pytest.mark.parametrize("arch", ["spikingformer-4-256", "spikingformer-lm"])
def test_model_grads_bitwise_fused_vs_off(arch):
    cfg, params, batch = _model_setup(arch)

    def loss(p, eng):
        with E.use_engine(eng):
            logits, _ = registry.forward(p, cfg, batch)
        return jnp.sum(logits ** 2) * 1e-3

    grads = {ov: jax.grad(loss)(params, cfg.engine.replace(overlap=ov))
             for ov in ("off", "fused")}
    for a, b in zip(jax.tree_util.tree_leaves(grads["off"]),
                    jax.tree_util.tree_leaves(grads["fused"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_logits_bitwise_under_jit():
    """Explicit overlap='fused' is honored under jit (the forward runs
    inside the block scan, so the bundle input is always traced)."""
    cfg, params, batch = _model_setup("spikingformer-4-256")
    outs = {}
    for ov in ("off", "fused"):
        eng = cfg.engine.replace(overlap=ov)

        @jax.jit
        def f(p):
            with E.use_engine(eng):
                return registry.forward(p, cfg, batch)[0]

        outs[ov] = np.asarray(f(params))
    np.testing.assert_array_equal(outs["off"], outs["fused"])


@pytest.mark.parametrize("arch,ov", [
    pytest.param("spikingformer-4-256", "off", id="off"),
    pytest.param("spikingformer-4-256", "fused", id="fused"),
    pytest.param("spikingformer-8-512", "off", id="8-512-off"),
    pytest.param("spikingformer-8-512", "fused", id="8-512-fused"),
])
def test_annotations_are_bitwise_neutral(arch, ov):
    """Every program scope (stem, blocks, head, LIF, both engines, the
    layer program) is metadata: logits match with scopes disabled."""
    cfg, params, batch = _model_setup(arch)
    eng = cfg.engine.replace(overlap=ov)
    with E.use_engine(eng):
        annotated, _ = registry.forward(params, cfg, batch)
        with E.disable_annotations():
            plain, _ = registry.forward(params, cfg, batch)
    np.testing.assert_array_equal(np.asarray(annotated), np.asarray(plain))


# ---------------------------------------------------------------------------
# schedule extension: scalar path pinned, per-head + measured metrics
# ---------------------------------------------------------------------------


def test_measured_schedule_scalar_path_pinned():
    ts, tb, heads = 1.3, 0.7, 8
    se, be, overlapped, serial = de.measured_schedule(ts, tb, heads)
    # the original two-scalar arithmetic, replayed op-for-op
    t_sparse = 0.0
    qk_done, v_done = {}, {}
    for h in range(heads):
        for name in ("Q", "K", "V"):
            t_sparse += ts
            if name == "K":
                qk_done[h] = t_sparse
            if name == "V":
                v_done[h] = t_sparse
    t_bin = 0.0
    for h in range(heads):
        t_bin = max(t_bin, qk_done[h]) + tb
        t_bin = max(t_bin, v_done[h]) + tb
    assert overlapped == max(t_sparse, t_bin)
    assert serial == t_sparse + 2 * tb * heads
    assert len(se) == 3 * heads and len(be) == 2 * heads


def test_measured_schedule_per_head_matches_uniform_scalar():
    heads = 4
    uniform = de.measured_schedule(2.0, 1.0, heads)
    per_head = de.measured_schedule([(2.0, 2.0, 2.0)] * heads,
                                    [(1.0, 1.0)] * heads, heads)
    assert uniform[2] == per_head[2]          # overlapped makespan
    assert uniform[3] == per_head[3]          # serial total
    assert uniform[0] == per_head[0] and uniform[1] == per_head[1]


def test_measured_schedule_rejects_length_mismatch():
    with pytest.raises(ValueError):
        de.measured_schedule([1.0, 2.0], 1.0, heads=4)


def test_schedule_metrics_utilization():
    m = de.schedule_metrics(1.0, 1.0, heads=4)
    assert 0.0 < m["hidden_fraction"] < 1.0
    assert 0.0 < m["sparse_util"] <= 1.0
    assert 0.0 < m["binary_util"] <= 1.0
    assert m["hidden_fraction"] == pytest.approx(
        de.measured_overlap_efficiency(1.0, 1.0, 4))
    # sparse engine never stalls in the Fig. 5 schedule
    assert m["sparse_util"] == pytest.approx(
        3 * 4 * 1.0 / m["overlapped"])


def test_fused_step_metrics_from_kernel_counts():
    t, b, l, k, heads, hd = 2, 2, 16, 32, 2, 16
    x, w3, _, aux = _bundle(jax.random.PRNGKey(11), t, b, l, k, heads, hd,
                            family="bn", dark_slab=True)
    _, cnt = fused_ssa(x, w3, None, aux, 0.3, family="bn",
                       num_heads=heads, head_dim=hd,
                       scale=1.0 / math.sqrt(hd))
    m = de.fused_step_metrics(np.asarray(cnt), seq=l, k_dim=k, head_dim=hd,
                              t_steps=t, batch=b)
    assert m["executed_attn"] == 2 * t * b * heads
    # the dark slab was skipped in all three projections of both heads
    assert m["executed_q"] == (t * b - 1) * heads
    assert m["proj_skip_fraction"] == pytest.approx(1.0 / (t * b))
    assert 0.0 < m["hidden_fraction"] < 1.0
    assert m["step_reduction"] > 0.0
    assert m["possible_steps"] == 5 * t * b * heads
