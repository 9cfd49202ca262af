"""The layer program (kernels/fused_layer.py, engine.layer_step).

Pins, in order of the stack:
  * the whole-layer kernel (SSA bundle + output projection + spiking
    MLP as one Pallas grid) is bitwise equal to the *jitted* sequential
    oracle (``reference_layer``) for both epilogue families and
    ``sparse in {tile, decoded}``, across non-divisible L, dark time
    slabs, all-zero inputs, random densities and int8-quantized
    weights. The oracle must be jitted: the kernel body is always
    compiled and compiled dots FMA-contract, so the eager reference is
    NOT the contract (see tests/test_spike_decode.py);
  * the ``(H, 8, n_l_blocks)`` occupancy map is exact;
  * the sequential SSA bundle of train mode and ineligible layers
    (``ssa_step`` / ``ssa_step_causal``) equals the eval path's
    ``reference_bundle`` bitwise;
  * the plan is static: ``overlap`` (off | fused) and ``sparse`` (tile |
    decoded) are read as set, eagerly and under jit alike, ``auto`` and
    ``pipeline`` are refused, and ineligible layers (gated MLP, biased
    linears) take the sequential fallback instead of the kernel;
  * whole-model logits AND grads are bitwise identical across
    ``overlap in {off, fused}`` x ``sparse in {tile, decoded}`` on the
    spikingformer configs — also under jit and with int8-quantized
    weights (eligible layers share one custom-VJP step, so all modes
    run one gradient program: ``engine._fused_layer``); profiler scopes
    are bitwise-neutral;
  * ``fused_step_metrics`` (layer event schedule, binary-hidden
    fraction), the Fig. 5 ``measured_schedule`` and the ``sim/balance_sim
    .binary_block_schedule`` numpy twin;
  * the bench-regression gate fails loud on stale baseline key families
    and enforces the layer hidden-fraction floor even at
    ``--update-baselines`` time (negative-tested).

Bit-exactness strategy: dyadic-grid weights make fp32 accumulation
order-exact, so equality is to the bit.
"""
import json
import math
import os
import sys

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
from repro.core.spiking import SpikingConfig, lif_scan
from repro.kernels import fused_layer as FL
from repro.models import registry
from repro.sim.balance_sim import binary_block_schedule

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))


def _dyadic(key, shape):
    return (jax.random.randint(key, shape, -128, 128)
            .astype(jnp.float32)) * (2.0 ** -8)


def _bn_rows(key, n):
    k1, k2 = jax.random.split(key)
    return jnp.stack([_dyadic(k1, (n,)) * 0.25,
                      jnp.abs(_dyadic(k2, (n,))) + 0.5,
                      jnp.ones((n,)) * 1.25,
                      jnp.full((n,), 0.0625)])


def _layer_ops(key, t, b, l, d, heads, hd, ff, *, family, quant=False,
               density=0.3):
    """Raw kernel operands (the layout ``engine.layer_step`` builds),
    with a dark (t=0, b=0) slab and an all-zero row."""
    q_dim = heads * hd
    ks = jax.random.split(key, 8)
    x = (jax.random.uniform(ks[0], (t, b, l, d)) < density
         ).astype(jnp.float32)
    x = x.at[:, :, min(2, l - 1)].set(0.0)
    x = x.at[0, 0].set(0.0)
    if quant:
        def qw(k, shape, n):
            return (jax.random.randint(k, shape, -128, 128)
                    .astype(jnp.int8).astype(jnp.float32),
                    jnp.abs(_dyadic(jax.random.fold_in(k, 1), (n,))) + 0.5)
        w3, sc3 = qw(ks[1], (3, d, q_dim), q_dim)
        sc3 = jnp.broadcast_to(sc3, (3, q_dim))
        wo, sco = qw(ks[2], (q_dim, d), d)
        w1, sc1 = qw(ks[3], (d, ff), ff)
        w2, sc2 = qw(ks[4], (ff, d), d)
        scales = (sc3, sco, sc1, sc2)
    else:
        w3 = _dyadic(ks[1], (3, d, q_dim))
        wo = _dyadic(ks[2], (q_dim, d))
        w1 = _dyadic(ks[3], (d, ff))
        w2 = _dyadic(ks[4], (ff, d))
        scales = None
    if family == "bn":
        auxp = jnp.stack([_bn_rows(k, q_dim)
                          for k in jax.random.split(ks[5], 3)])
        auxo = _bn_rows(ks[6], d)
        aux1 = _bn_rows(jax.random.fold_in(ks[6], 1), ff)
        aux2 = _bn_rows(jax.random.fold_in(ks[6], 2), d)
        s = lif_scan(x, SpikingConfig(time_steps=t))[0]
    else:
        half = hd // 2
        freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs
        auxp = jnp.stack([jnp.cos(ang), jnp.sin(ang)])
        auxo = jnp.ones((1, d), jnp.float32)
        aux1 = aux2 = None
        x32 = x.astype(jnp.float32)
        s = (x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
        ).astype(x.dtype)
    # the engine zero-pads d_ff to a heads multiple before the kernel
    # boundary (exact — identity BN rows, zero up/down pad)
    sc1 = scales[2] if quant else jnp.ones((ff,), jnp.float32)
    w1, w2, sc1, aux1 = E._pad_ff(w1, w2, sc1, aux1, heads)
    if quant:
        scales = (scales[0], scales[1], sc1, scales[3])
    return (x, s, w3, wo, w1, w2, scales, auxp, auxo, aux1, aux2, 0.3)


# (t, b, l, d, heads, hd, ff): non-divisible L vs l_block=8, ff not a
# heads multiple (exercises the exact zero-pad)
SHAPE = (2, 2, 13, 16, 2, 8, 21)
# more layer shapes: non-divisible L, an L of whole blocks, and odd
# everything (even head_dim for RoPE); q_dim != d in the first and last
SHAPES = [(2, 2, 13, 24, 4, 8, 42),
          (2, 1, 16, 32, 2, 16, 64),
          (3, 2, 9, 17, 3, 6, 35)]
L_BLOCK, C_BLOCK = 8, 8


def _run(args, family, sparse, shape=SHAPE):
    heads, hd = shape[4], shape[5]
    kw = dict(family=family, num_heads=heads, head_dim=hd,
              scale=1.0 / math.sqrt(hd), causal=family == "rope")
    out, cnt = FL.fused_layer(*args, sparse=sparse, l_block=L_BLOCK,
                              c_block=C_BLOCK, **kw)
    scfg = SpikingConfig(time_steps=shape[0])
    ref = jax.jit(lambda *a: FL.reference_layer(*a, scfg, **kw))(*args)
    return out, cnt, ref


@pytest.mark.parametrize("family,sparse", [("bn", "tile"),
                                           ("bn", "decoded"),
                                           ("rope", "tile")])
@pytest.mark.parametrize("shape", SHAPES)
def test_layer_kernel_matches_jitted_oracle_bitwise(family, sparse, shape):
    t, b, l, d, heads, hd, ff = shape
    args = _layer_ops(jax.random.PRNGKey(11), t, b, l, d, heads, hd, ff,
                      family=family)
    out, cnt, ref = _run(args, family, sparse, shape)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    cnt = np.asarray(cnt)
    assert cnt.shape == (heads, 8, -(-l // L_BLOCK))
    if family == "bn" and sparse == "tile":
        # dark (t=0, b=0) slab skipped in every projection phase/block
        assert (cnt[:, :3].sum(axis=-1) <= 3 * (t * b - 1)).all()


@pytest.mark.parametrize("family", ["bn", "rope"])
def test_layer_kernel_int8_weights_bitwise(family):
    t, b, l, d, heads, hd, ff = SHAPE
    args = _layer_ops(jax.random.PRNGKey(9), t, b, l, d, heads, hd, ff,
                      family=family, quant=True)
    for sparse in ("tile", "decoded"):
        out, _, ref = _run(args, family, sparse)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _random_density_bitwise(family, seed, density):
    t, b, l, d, heads, hd, ff = SHAPE
    args = _layer_ops(jax.random.PRNGKey(seed), t, b, l, d, heads, hd, ff,
                      family=family, density=density)
    out, _, ref = _run(args, family, "tile")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10 ** 6), st.floats(0.05, 0.6))
def test_layer_kernel_property_random_density_bn(seed, density):
    _random_density_bitwise("bn", seed, density)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10 ** 6), st.floats(0.05, 0.6))
def test_layer_kernel_property_random_density_rope(seed, density):
    _random_density_bitwise("rope", seed, density)


def test_layer_kernel_all_zero_timestep():
    t, b, l, d, heads, hd, ff = SHAPE
    args = _layer_ops(jax.random.PRNGKey(3), t, b, l, d, heads, hd, ff,
                      family="bn")
    args = (jnp.zeros_like(args[0]), jnp.zeros_like(args[1])) + args[2:]
    out, cnt, ref = _run(args, "bn", "tile")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # every projection slab dark -> zero executed projection sub-blocks
    np.testing.assert_array_equal(np.asarray(cnt)[:, :3], 0)


def test_binary_block_schedule_twin_matches_kernel_counts():
    t, b, l, d, heads, hd, ff = SHAPE
    args = _layer_ops(jax.random.PRNGKey(13), t, b, l, d, heads, hd, ff,
                      family="bn")
    _, cnt, _ = _run(args, "bn", "tile")
    # the twin predicts the binary phases from the projection spikes the
    # kernel emits; recompute them under jit (compiled dots contract)
    scfg = SpikingConfig(time_steps=t)

    @jax.jit
    def kv(s, w3, auxp):
        out = []
        for i in (1, 2):
            cur = jnp.dot(s, w3[i], preferred_element_type=jnp.float32)
            y = cur.astype(s.dtype).astype(jnp.float32)
            y = (y - auxp[i, 0]) * jax.lax.rsqrt(auxp[i, 1] + 1e-5)
            y = (y * auxp[i, 2] + auxp[i, 3]).astype(s.dtype)
            out.append(lif_scan(y, scfg)[0])
        return tuple(out)

    ksp, vsp = kv(args[1], args[2], args[7])
    pred = binary_block_schedule(np.asarray(ksp), np.asarray(vsp), heads,
                                 L_BLOCK, 0.3)
    np.testing.assert_array_equal(pred, np.asarray(cnt)[:, 3:5, :])


def test_binary_block_schedule_predicate_edges():
    k = np.zeros((2, 1, 8, 4))
    v = np.ones((2, 1, 8, 4))
    # all-dark keys: nothing live under binarize with delta > 0 ...
    out = binary_block_schedule(k, v, 1, 4, delta=0.3)
    np.testing.assert_array_equal(out, 0)
    # ... everything qkt-live when delta <= 0 or scores stay analog
    # (zeros binarize to ones at delta <= 0, so the block must execute)
    for kw in (dict(delta=0.0), dict(delta=0.3, binarize=False)):
        out = binary_block_schedule(k, v, 1, 4, **kw)
        np.testing.assert_array_equal(out[:, 0], 2)  # t*b per block
        np.testing.assert_array_equal(out[:, 1], 2)  # live v rides along
        # ... but a dark value block still kills the context phase
        out = binary_block_schedule(k, np.zeros_like(v), 1, 4, **kw)
        np.testing.assert_array_equal(out[:, 1], 0)


# ---------------------------------------------------------------------------
# the SSA bundle: the sequential steps vs the eval layer's oracle bundle
# ---------------------------------------------------------------------------


def _one_layer(arch, quant=None):
    """(cfg, layer params, BN state) of ``arch``'s SMOKE config, dyadic
    (optionally int8); the BN state is ``None`` for the token family."""
    cfg, params, _ = _model_setup(arch, quant)
    if cfg.family == "dense":
        return cfg, jax.tree_util.tree_map(lambda a: a[0],
                                           params["layers"]), None
    from repro.models import spikingformer
    bst = spikingformer.init_state(cfg)["blocks"]
    return (cfg, jax.tree_util.tree_map(lambda a: a[0], params["blocks"]),
            jax.tree_util.tree_map(lambda a: a[0], bst))


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("family", ["bn", "rope"])
def test_sequential_bundle_matches_reference_bundle(family, quant):
    """The SSA bundle that train mode and ineligible layers run
    (``ssa_step`` / ``ssa_step_causal``, composed from the engine's
    primitives) equals the bundle the eval layer program runs on the
    chip (``reference_bundle``), bitwise, eagerly and under jit."""
    arch = "spikingformer-4-256" if family == "bn" else "spikingformer-lm"
    cfg, p, bst = _one_layer(arch, quant)
    t, b, l, d = cfg.spiking.time_steps, 2, 13, cfg.d_model
    hd, names = cfg.head_dim, ("wq", "wk", "wv")
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    if family == "bn":
        # running stats off the identity, and BN affines that make the
        # projections fire
        bst = {f"bn_{n}": {"mean": _dyadic(ks[i], (cfg.q_dim,)) * 0.25,
                           "var": jnp.abs(_dyadic(ks[i], (cfg.q_dim,))) + 0.5}
               for i, n in enumerate("qkv")}
        p = dict(p, **{f"bn_{n}": {"scale": jnp.full((cfg.q_dim,), 2.0),
                                   "bias": jnp.full((cfg.q_dim,), 0.5)}
                       for n in "qkv"})
        s = lif_scan(jax.random.normal(ks[3], (t, b, l, d)),
                     cfg.spiking)[0]
        aux = jnp.stack([E._bn_rows(p, bst, f"bn_{n}") for n in "qkv"])
        seq = lambda p, s: E.ssa_step(p, bst, cfg, s)[0]
    else:
        pos = jnp.arange(l)
        s = jax.random.normal(ks[3], (t, b, l, d))
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
        ang = pos.astype(jnp.float32)[:, None] * freqs
        aux = jnp.stack([jnp.cos(ang), jnp.sin(ang)])
        seq = lambda p, s: E.ssa_step_causal(p, cfg, s, pos)
    if quant:
        w3, sc3 = E._layer_quant_w3(p, names, d, s.dtype)
    else:
        w3, sc3 = jnp.stack([p[w]["w"] for w in names]), None

    def ref(s, w3, sc3, aux, delta):
        return FL.reference_bundle(
            s, w3, sc3, aux, delta, cfg.spiking, family=family,
            num_heads=cfg.num_heads, head_dim=hd, scale=1.0 / math.sqrt(hd),
            causal=family == "rope")

    with E.use_engine(cfg.engine):
        got, got_jit = seq(p, s), jax.jit(seq)(p, s)
        want = ref(s, w3, sc3, aux, p["delta"])
        want_jit = jax.jit(ref)(s, w3, sc3, aux, p["delta"])
    assert np.any(np.asarray(want) != 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_jit), np.asarray(want_jit))


# ---------------------------------------------------------------------------
# the static plan + sequential fallback
# ---------------------------------------------------------------------------


def _spy_programs(monkeypatch):
    """Record the layer program each call runs: the fused kernel (with
    the sparse datapath it was handed; stubbed to return the layer input,
    its results are pinned above) or the sequential oracle."""
    seen = []
    oracle = FL.reference_layer

    def spy_kernel(*a, **k):
        seen.append(("fused", k["sparse"]))
        return a[0], None

    def spy_oracle(*a, **k):
        seen.append(("off",))
        return oracle(*a, **k)

    monkeypatch.setattr(FL, "fused_layer", spy_kernel)
    monkeypatch.setattr(FL, "reference_layer", spy_oracle)
    return seen


def _eager_and_jit(f, *args):
    return f(*args), jax.jit(f)(*args)


def test_resolve_overlap_modes(monkeypatch):
    """``overlap`` is read as set: 'fused' runs the layer kernel, 'off'
    (and no engine) the sequential oracle — eagerly and under jit alike,
    whatever the input's size."""
    seen = _spy_programs(monkeypatch)
    cfg, p, bst = _one_layer("spikingformer-4-256")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 8, cfg.d_model))
    for eng, want in ((None, ("off",)),
                      (E.EngineConfig(overlap="off"), ("off",)),
                      (E.EngineConfig(overlap="fused"), ("fused", "tile"))):
        seen.clear()
        _eager_and_jit(lambda x, e=eng: E.layer_step(p, bst, cfg, x,
                                                     engine=e)[0], x)
        assert seen == [want, want], (eng, seen)


def test_resolve_layer_plan_rules(monkeypatch):
    """The plan is (overlap, sparse) as set: the kernel is handed the
    engine's sparse datapath on both layer families, eagerly and under
    jit."""
    seen = _spy_programs(monkeypatch)
    for arch in ("spikingformer-4-256", "spikingformer-lm"):
        cfg, p, bst = _one_layer(arch)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (2, 1, 8, cfg.d_model))
        for sparse in ("tile", "decoded"):
            eng = E.EngineConfig(overlap="fused", sparse=sparse)
            if bst is None:
                f = lambda x, e=eng: E.layer_step_causal(
                    p, cfg, x, jnp.arange(8), engine=e)
            else:
                f = lambda x, e=eng: E.layer_step(p, bst, cfg, x,
                                                  engine=e)[0]
            seen.clear()
            _eager_and_jit(f, x)
            assert seen == [("fused", sparse)] * 2, (arch, seen)


@pytest.mark.parametrize("overlap", ["pipelined", "auto", "pipeline"])
def test_engine_config_rejects_bad_overlap(overlap):
    with pytest.raises(ValueError, match="overlap mode"):
        E.EngineConfig(overlap=overlap)


@pytest.mark.parametrize("arch", ["spikingformer-4-256",
                                  "spikingformer-8-512",
                                  "spikingformer-lm"])
def test_layer_step_eager_equals_jit(arch, monkeypatch):
    """An eager ``layer_step`` call runs the program every jitted step
    runs, at the config's own engine and at a shape above ``min_flops``
    (a per-call ``auto`` would fuse it eagerly and not under jit).
    Weights sit on the dyadic grid and BN running variances at 1 - eps,
    so the vision layer's arithmetic is exact and XLA's FMA contraction
    under jit cannot split the two calls by an ulp: what is compared is
    the program."""
    seen = _spy_programs(monkeypatch)
    cfg, p, bst = _one_layer(arch)
    t, b, l, d = cfg.spiking.time_steps, 4, 64, cfg.d_model
    assert 6 * t * b * l * d * cfg.q_dim >= cfg.engine.min_flops
    x = jax.random.normal(jax.random.PRNGKey(3), (t, b, l, d))
    if bst is None:
        f = lambda x: E.layer_step_causal(p, cfg, x, jnp.arange(l),
                                          engine=cfg.engine)
    else:
        one = jnp.float32(1.0) - jnp.float32(1e-5)
        assert one + jnp.float32(1e-5) == 1.0
        bst = {n: {"mean": v["mean"], "var": jnp.full_like(v["var"], one)}
               for n, v in bst.items()}
        f = lambda x: E.layer_step(p, bst, cfg, x, engine=cfg.engine)[0]
    eager, jitted = _eager_and_jit(f, x)
    assert seen == [("off",)] * 2, seen
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


def test_ineligible_layer_takes_sequential_fallback(monkeypatch):
    """A layer the fused program has no mapping for (gated MLP, biased
    linear) must run the sequential composition — pinned by making the
    kernel explode and checking only the eligible layer reaches it."""
    from repro.models import nn, transformer

    def boom(*a, **k):
        raise AssertionError("fused kernel reached for ineligible layer")

    monkeypatch.setattr(FL, "fused_layer", boom)
    cfg = get_config("spikingformer-lm", smoke=True)
    p = jax.tree_util.tree_map(
        lambda a: a[0], registry.init(cfg, jax.random.PRNGKey(0))["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.spiking.time_steps, 1, 8, cfg.d_model))
    pos = jnp.arange(8)
    eng = cfg.engine.replace(overlap="fused")
    with pytest.raises(AssertionError, match="ineligible"):
        E.layer_step_causal(p, cfg, x, pos, engine=eng)
    gated = dict(p, mlp=dict(p["mlp"], gate=nn.linear_init(
        jax.random.PRNGKey(2), cfg.d_model, cfg.d_ff)))
    out = E.layer_step_causal(gated, cfg, x, pos, engine=eng)
    assert out.shape == x.shape
    # ... and the fallback matches the model's own pre-engine layer
    # composition: overlap='off' without the kernel still works
    off = E.layer_step_causal(p, cfg, x, pos,
                              engine=cfg.engine.replace(overlap="off"))
    assert off.shape == x.shape


# ---------------------------------------------------------------------------
# whole-model parity: logits + grads across all modes
# ---------------------------------------------------------------------------


SPIKING_ARCHS = ["spikingformer-4-256", "spikingformer-8-512",
                 "spikingformer-lm"]
MODES = [("off", "tile"), ("fused", "tile"), ("fused", "decoded")]


def _model_setup(arch, quant=None):
    cfg = get_config(arch, smoke=True)
    params = jax.tree_util.tree_map(
        lambda a: jnp.round(a * 256) / 256
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        registry.init(cfg, jax.random.PRNGKey(0)))
    if quant:
        from repro.quant import quantize_tree
        params = quantize_tree(params, quant, dyadic=True)
    if cfg.family == "dense":
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (2, 16), 0, cfg.vocab_size)}
    else:
        batch = {"images": jax.random.uniform(
            jax.random.PRNGKey(1),
            (2, cfg.vision.img_size, cfg.vision.img_size,
             cfg.vision.in_channels))}
    return cfg, params, batch


def _mode_logits(cfg, params, batch, modes):
    outs = []
    for ov, sp in modes:
        with E.use_engine(cfg.engine.replace(overlap=ov, sparse=sp)):
            logits, _ = registry.forward(params, cfg, batch)
        outs.append(np.asarray(logits))
    return outs


@pytest.mark.parametrize("arch", SPIKING_ARCHS)
def test_model_logits_bitwise_all_modes(arch):
    cfg, params, batch = _model_setup(arch)
    modes = MODES if cfg.family != "dense" else \
        [m for m in MODES if m[1] == "tile"]  # decoded is spike-driven
    outs = _mode_logits(cfg, params, batch, modes)
    for got in outs[1:]:
        np.testing.assert_array_equal(outs[0], got)


@pytest.mark.parametrize("arch,sparse", [("spikingformer-4-256", "decoded"),
                                         ("spikingformer-lm", "tile")])
def test_model_grads_bitwise_all_modes(arch, sparse):
    cfg, params, batch = _model_setup(arch)

    def loss(p, eng):
        with E.use_engine(eng):
            logits, _ = registry.forward(p, cfg, batch)
        return jnp.sum(logits ** 2) * 1e-3

    grads = [jax.grad(loss)(params,
                            cfg.engine.replace(overlap=ov, sparse=sparse))
             for ov in ("off", "fused")]
    for g in grads[1:]:
        for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_int8_logits_and_grads_bitwise():
    cfg, params, batch = _model_setup("spikingformer-4-256", quant="int8")
    outs = _mode_logits(cfg, params, batch,
                        [("off", "tile"), ("fused", "decoded"),
                         ("fused", "tile")])
    for got in outs[1:]:
        np.testing.assert_array_equal(outs[0], got)

    def loss(p, eng):
        with E.use_engine(eng):
            logits, _ = registry.forward(p, cfg, batch)
        return jnp.sum(logits ** 2) * 1e-3

    # int8 code leaves take float0 grads (allow_int); the fp leaves —
    # scales, norms, head — must still agree bitwise across modes
    ga = jax.grad(loss, allow_int=True)(params,
                                        cfg.engine.replace(overlap="off"))
    gb = jax.grad(loss, allow_int=True)(
        params, cfg.engine.replace(overlap="fused"))
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["spikingformer-4-256",
                                  "spikingformer-lm"])
def test_model_logits_bitwise_under_jit(arch):
    """Explicit overlap='fused' is honored under jit (the layer sits
    inside the block scan, so its input is always traced)."""
    cfg, params, batch = _model_setup(arch)
    outs = {}
    for ov in ("off", "fused"):
        eng = cfg.engine.replace(overlap=ov)

        @jax.jit
        def f(p):
            with E.use_engine(eng):
                return registry.forward(p, cfg, batch)[0]

        outs[ov] = np.asarray(f(params))
    np.testing.assert_array_equal(outs["off"], outs["fused"])


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
# schedules: the Fig. 5 measured schedule and the occupancy-map metrics
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


def _layer_metrics(counts, **over):
    kw = dict(seq=16, k_dim=16, head_dim=8, t_steps=2, batch=2,
              d_model=16, d_ff=32, l_block=8, sparse="tile",
              c_block=None)
    kw.update(over)
    return de.fused_step_metrics(counts, **kw)


def test_layer_metrics_counts_and_bounds():
    full = 2 * 2  # t * b possible per (head, phase, block); heads=2, nlb=2
    counts = np.full((2, 8, 2), full, np.int64)
    m = _layer_metrics(counts)
    assert m["executed_steps"] == counts.sum()
    assert m["possible_steps"] == 8 * 2 * 2 * full
    assert m["step_reduction"] == 0.0
    assert 0.0 <= m["hidden_fraction"] <= 1.0
    assert m["sparse_util"] <= 1.0 and m["binary_util"] <= 1.0
    # decoded projections: q/k/v possible scale by the c_block chunks
    md = _layer_metrics(counts, sparse="decoded", c_block=8)
    assert md["possible_steps"] > m["possible_steps"]
    # half the counts -> half the executed steps
    mh = _layer_metrics(counts // 2)
    assert mh["executed_steps"] == m["executed_steps"] // 2


def test_layer_metrics_degenerate_schedules():
    # binary-only work: nothing to hide behind -> hidden fraction 0
    counts = np.zeros((1, 8, 1), np.int64)
    counts[:, 3:5] = 4
    m = _layer_metrics(counts)
    assert m["hidden_fraction"] == 0.0
    # sparse-only work: no binary busy time -> defined as 0
    counts = np.zeros((1, 8, 1), np.int64)
    counts[:, :3] = 4
    assert _layer_metrics(counts)["hidden_fraction"] == 0.0


def test_layer_event_schedule_dependencies():
    macs = {ph: [10.0] for ph in de.LAYER_PHASE_NAMES}
    se, be = de.layer_event_schedule(macs, heads=1)
    ends = {n: e for n, _, e in se}
    starts = {n: s for n, s, _ in be}
    # binary qkt waits for the sparse k phase; qktv for v
    assert starts["qkt0"] >= ends["k0"]
    assert starts["qktv0"] >= ends["v0"]
    # sparse wo stalls on the binary context (qktv) of its head
    wo_start = [s for n, s, _ in se if n == "wo0"][0]
    qktv_end = [e for n, _, e in be if n == "qktv0"][0]
    assert wo_start >= qktv_end


# ---------------------------------------------------------------------------
# bench-regression gate: stale families + floors (negative tests)
# ---------------------------------------------------------------------------


def _gate_dirs(tmp_path):
    import check_regression as cr
    art = tmp_path / "artifacts"
    base = tmp_path / "baselines"
    art.mkdir(), base.mkdir()
    here = os.path.join(os.path.dirname(__file__), "..")
    for name in cr.SPECS:
        with open(os.path.join(here, "benchmarks", "baselines", name)) as f:
            pairs = json.load(f)
        (base / name).write_text(json.dumps(pairs))
    for name in cr.SPECS:
        with open(os.path.join(here, "artifacts", name)) as f:
            (art / name).write_text(f.read())
    return cr, str(art), str(base)


def test_gate_fails_loud_on_stale_baseline_family(tmp_path, capsys):
    cr, art, base = _gate_dirs(tmp_path)
    assert cr.check(art, base, update=False) == 0
    bp = os.path.join(base, "dual_engine_bench.json")
    with open(bp) as f:
        stale = json.load(f)
    stale["ghost_bench/some/metric"] = 1.0
    with open(bp, "w") as f:
        json.dump(stale, f)
    assert cr.check(art, base, update=False) == 1
    out = capsys.readouterr().out
    assert "stale baseline family 'ghost_bench'" in out


def test_gate_floor_holds_even_on_update(tmp_path, capsys):
    cr, art, base = _gate_dirs(tmp_path)
    ap = os.path.join(art, "dual_engine_bench.json")
    with open(ap) as f:
        blob = json.load(f)
    for r in blob["layer_rows"]:
        if r["config"] == "spikingformer-lm" and r["overlap"] != "off":
            r["hidden_fraction"] = 0.10          # below the 0.3971 floor
    with open(ap, "w") as f:
        json.dump(blob, f)
    assert cr.check(art, base, update=False) == 1
    assert "strictly above the floor" in capsys.readouterr().out
    # --update-baselines must refuse to ratify the below-floor artifact
    assert cr.check(art, base, update=True) == 1
    with open(os.path.join(base, "dual_engine_bench.json")) as f:
        kept = json.load(f)
    key = "layer/spikingformer-lm/fused/tile/hidden_fraction"
    assert kept[key] > 0.3971                    # old baseline untouched
