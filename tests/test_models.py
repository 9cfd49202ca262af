"""Model-family correctness: forward/decode consistency, spiking mode,
MoE invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis; use fixed-seed shim
    from _propcheck import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.core.spiking import SpikingConfig
from repro.models import moe as moe_mod, registry


def _decode_vs_forward(arch, n=10, max_len=24):
    cfg = get_config(arch, smoke=True)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, n)), jnp.int32)
    batch = {"tokens": toks}
    if cfg.family == "encdec":
        batch["audio_embeds"] = jnp.asarray(
            rng.normal(0, 0.1, (2, cfg.encoder_seq,
                                cfg.d_model)).astype(np.float32))
    logits, _ = registry.forward(params, cfg, batch)
    cache = registry.init_cache(cfg, 2, max_len, batch=batch, params=params)
    step = jax.jit(lambda c, t, p: registry.decode_step(params, cfg, c, t, p))
    outs = []
    for i in range(n):
        lg, cache = step(cache, toks[:, i:i + 1], jnp.asarray(i, jnp.int32))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["nemotron-4-15b", "gemma3-12b",
                                  "h2o-danube-3-4b", "granite-20b",
                                  "deepseek-moe-16b", "rwkv6-3b",
                                  "hymba-1.5b", "whisper-small",
                                  "mellum2-12b-a2.5b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode == full-sequence forward (all cache kinds:
    full, rolling-window, local+global, MoE, expert layers in the dense
    family, recurrent states)."""
    _decode_vs_forward(arch)


def test_vlm_decode_continues_prefill():
    cfg = get_config("llava-next-mistral-7b", smoke=True)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (2, 6)), jnp.int32),
             "patch_embeds": jnp.asarray(
                 rng.normal(0, 0.1, (2, cfg.frontend.num_embeds,
                                     cfg.frontend.embed_dim)).astype(
                     np.float32))}
    logits, _ = registry.forward(params, cfg, batch)
    assert logits.shape[1] == 6 + cfg.frontend.num_embeds
    cache = registry.init_cache(cfg, 2, 32)
    lg, cache = registry.decode_step(params, cfg, cache,
                                     batch["tokens"][:, :1],
                                     jnp.asarray(0, jnp.int32))
    assert np.isfinite(np.asarray(lg)).all()


def test_spiking_dense_lm_binary_activations():
    cfg = get_config("h2o-danube-3-4b", smoke=True).replace(
        spiking=SpikingConfig(time_steps=2))
    params = registry.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.ones((2, 8), jnp.int32)
    logits, _ = registry.forward(params, cfg, {"tokens": toks}, train=True)
    assert np.isfinite(np.asarray(logits)).all()
    g = jax.grad(lambda p: registry.forward(
        p, cfg, {"tokens": toks}, train=True)[0].sum())(params)
    total = sum(float(jnp.abs(l).sum())
                for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(total) and total > 0


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------

_MCFG = ModelConfig(
    name="m", family="moe", num_layers=2, d_model=32, num_heads=4,
    num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64, dtype="float32",
    remat=False, moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=16))


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.integers(4, 64), st.integers(1, 4))
def test_router_topk_invariants(t, k):
    m = MoEConfig(num_experts=8, top_k=k, d_ff_expert=16)
    x = jax.random.normal(jax.random.PRNGKey(t), (t, 16))
    w_router = jax.random.normal(jax.random.PRNGKey(k), (16, 8))
    w, idx, aux_lb, aux_z = moe_mod.router_topk(x, w_router, m)
    assert w.shape == (t, k) and idx.shape == (t, k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert (np.asarray(idx) >= 0).all() and (np.asarray(idx) < 8).all()
    # per row, indices distinct
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == k
    assert float(aux_lb) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz, = 1 uniform


def test_moe_dispatch_matches_dense_at_high_capacity():
    """With capacity >= tokens, sort-based dispatch == explicit per-token
    expert mixture."""
    m = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8,
                  capacity_factor=8.0)
    t, d = 12, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (t, d))
    up = jax.random.normal(ks[1], (4, d, 8)) * 0.3
    gate = jax.random.normal(ks[2], (4, d, 8)) * 0.3
    down = jax.random.normal(ks[3], (4, 8, d)) * 0.3
    w = jax.nn.softmax(jax.random.normal(ks[4], (t, 4)), -1)
    wk, idx = jax.lax.top_k(w, 2)
    wk = wk / wk.sum(-1, keepdims=True)
    got = moe_mod._dispatch_local(x, wk, idx, up, gate, down, m, "silu",
                                  4, 0)
    want = np.zeros((t, d), np.float32)
    for i in range(t):
        for j in range(2):
            e = int(idx[i, j])
            h = jax.nn.silu(x[i] @ gate[e]) * (x[i] @ up[e])
            want[i] += float(wk[i, j]) * np.asarray(h @ down[e])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_overflow():
    m = MoEConfig(num_experts=2, top_k=1, d_ff_expert=4,
                  capacity_factor=0.5)
    t, d = 8, 8
    x = jnp.ones((t, d))
    up = jnp.ones((2, d, 4)) * 0.1
    gate = jnp.ones((2, d, 4)) * 0.1
    down = jnp.ones((2, 4, d)) * 0.1
    w = jnp.ones((t, 1))
    idx = jnp.zeros((t, 1), jnp.int32)  # everyone wants expert 0
    got = moe_mod._dispatch_local(x, w, idx, up, gate, down, m, "silu", 2, 0)
    served = (np.abs(np.asarray(got)).sum(-1) > 0).sum()
    assert served == 2  # capacity = ceil(8*1/2*0.5) = 2


# ---------------------------------------------------------------------------
# expert layer of the dense family: held shares, dropless dispatch
# ---------------------------------------------------------------------------


def _expert_cfg(held, width=16, top_k=4):
    return get_config("mellum2-12b-a2.5b", smoke=True).replace(
        moe=MoEConfig(num_experts=held, router_width=width, top_k=top_k,
                      d_ff_expert=32, capacity_factor=0.0))


def _expert_params(cfg, seed=0):
    return moe_mod.ffn_init(jax.random.PRNGKey(seed), cfg)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares (4 of 16 experts each, offsets 0, 4, 8, 12) add
    up to the layer that holds all 16."""
    whole_cfg = _expert_cfg(16)
    p = _expert_params(whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, whole_cfg.d_model))
    whole, _ = moe_mod.moe_ffn(p, x, whole_cfg)
    share_cfg = _expert_cfg(4)
    parts = []
    for off in (0, 4, 8, 12):
        sl = {k: (v if k == "router" else v[off:off + 4])
              for k, v in p.items()}
        parts.append(moe_mod.moe_ffn(sl, x, share_cfg, offset=off)[0])
    # each share alone is a real part of the layer, not all of it
    assert all(float(jnp.abs(y - whole).max()) > 1e-3 for y in parts)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)


def _explicit_mixture(p, x2d, cfg):
    """Per token: softmax over the router, top-k, renormalised, each
    picked expert's SwiGLU times its weight."""
    probs = jax.nn.softmax(x2d @ p["router"], -1)
    w, idx = jax.lax.top_k(probs, cfg.moe.top_k)
    w = w / w.sum(-1, keepdims=True)
    out = np.zeros(x2d.shape, np.float32)
    for i in range(x2d.shape[0]):
        for j in range(cfg.moe.top_k):
            e = int(idx[i, j])
            if e < p["up"].shape[0]:
                h = jax.nn.silu(x2d[i] @ p["gate"][e]) * (x2d[i] @ p["up"][e])
                out[i] += float(w[i, j]) * np.asarray(h @ p["down"][e])
    return out


def test_dropless_serves_every_token_on_one_expert():
    """Every token picks the same experts: the capacity dispatch of a
    training forward drops most of them, the serving path none."""
    cfg = _expert_cfg(4, width=4, top_k=2).replace(
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=1.25))
    p = _expert_params(cfg)
    # a router that sends every token to experts 0 and then 1
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
             .at[:, 1].set(0.5))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2),
                                  (24, cfg.d_model)))
    served, _ = moe_mod.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(served),
                               _explicit_mixture(p, x, cfg),
                               atol=1e-5, rtol=1e-5)
    # capacity ceil(24 * 2 / 4 * 1.25) = 15 rows an expert: the last 9
    # tokens reach neither of their experts
    dropped, _ = moe_mod.moe_ffn(p, x, cfg, train=True)
    assert (np.abs(np.asarray(dropped)).sum(-1) == 0).sum() == 9


def test_padded_rows_join_no_expert():
    """Rows marked invalid come out 0 and change no real row, whatever
    they hold."""
    cfg = _expert_cfg(4)
    p = _expert_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 5, cfg.d_model))
    valid = jnp.arange(5)[None, :] < jnp.asarray([5, 2, 0])[:, None]
    y, _ = moe_mod.moe_ffn(p, x, cfg, valid=valid)
    junk = jnp.where(valid[..., None], x, 1e4)
    y2, _ = moe_mod.moe_ffn(p, junk, cfg, valid=valid)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert float(jnp.abs(jnp.where(valid[..., None], 0.0, y)).max()) == 0.0
    want = _explicit_mixture(p, x.reshape(-1, cfg.d_model), cfg)
    got = np.asarray(y).reshape(-1, cfg.d_model)
    rows = np.asarray(valid).reshape(-1)
    np.testing.assert_allclose(got[rows], want[rows], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("held,top_k", [(8, 8), (4, 8), (16, 4)])
def test_combine_reads_no_row_past_the_groups(monkeypatch, held, top_k):
    """The grouped matmul leaves its rows past ``sizes.sum()`` undefined:
    made NaN here, they reach no output, padded rows included."""
    cfg = _expert_cfg(held, top_k=top_k)
    p = _expert_params(cfg)
    gmm = moe_mod.grouped_matmul

    def poisoned(lhs, rhs, sizes):
        out = gmm(lhs, rhs, sizes)
        past = jnp.arange(out.shape[0])[:, None] >= sizes.sum()
        return jnp.where(past, jnp.nan, out)
    monkeypatch.setattr(moe_mod, "grouped_matmul", poisoned)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, cfg.d_model))
    valid = jnp.arange(7)[None, :] < jnp.asarray([7, 3])[:, None]
    y, _ = moe_mod.moe_ffn(p, x, cfg, valid=valid)
    got = np.asarray(y).reshape(-1, cfg.d_model)
    rows = np.asarray(valid).reshape(-1)
    assert np.isfinite(got).all()
    assert (got[~rows] == 0).all()
    want = _explicit_mixture(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(got[rows], want[rows], atol=1e-5, rtol=1e-5)
