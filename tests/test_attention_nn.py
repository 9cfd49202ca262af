"""Chunked flash / banded / binary attention vs naive references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spiking import binarize
from repro.models import nn


def naive(q, k, v, *, causal=True, window=None, q_offset=0, kvl=None,
          scale=None):
    b, lq, h, d = q.shape
    _, lk, kh, _ = k.shape
    rep = h // kh
    scale = 1 / np.sqrt(d) if scale is None else scale
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
    qpos = q_offset + jnp.arange(lq)
    kpos = jnp.arange(lk)
    m = jnp.ones((lq, lk), bool)
    if causal:
        m &= kpos[None] <= qpos[:, None]
    if window:
        m &= kpos[None] > qpos[:, None] - window
    if kvl is not None:
        m &= (kpos < kvl)[None]
    s = jnp.where(m[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


def _qkv(lq=37, lk=53, h=8, kh=4, d=16, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, lq, h, d)),
            jax.random.normal(ks[1], (b, lk, kh, d)),
            jax.random.normal(ks[2], (b, lk, kh, d)))


@pytest.mark.parametrize("kwargs", [
    dict(causal=True), dict(causal=False), dict(causal=True, window=7),
    dict(causal=True, q_offset=16),
])
@pytest.mark.parametrize("chunks", [(16, 16), (8, 32), (64, 64)])
def test_flash_matches_naive(kwargs, chunks):
    q, k, v = _qkv()
    out = nn.flash_attention(q, k, v, q_chunk=chunks[0], kv_chunk=chunks[1],
                             **kwargs)
    want = naive(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_kv_valid_len():
    q, k, v = _qkv()
    out = nn.flash_attention(q, k, v, q_chunk=16, kv_chunk=16,
                             q_offset=16, kv_valid_len=40)
    want = naive(q, k, v, q_offset=16, kvl=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window,l", [(8, 64), (16, 128), (64, 96)])
def test_banded_matches_flash_window(window, l):
    q, k, v = _qkv(lq=l, lk=l, seed=3)
    got = nn.banded_flash_attention(q, k, v, window=window, q_chunk=16)
    want = naive(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_binary_flash_matches_dense_binary():
    b, l, h, kh, d = 2, 48, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = (jax.random.uniform(ks[0], (b, l, h, d)) > 0.75).astype(jnp.float32)
    k = (jax.random.uniform(ks[1], (b, l, kh, d)) > 0.75).astype(jnp.float32)
    v = (jax.random.uniform(ks[2], (b, l, kh, d)) > 0.75).astype(jnp.float32)
    got = nn.binary_flash_attention(q, k, v, delta=0.3, alpha=4.0,
                                    q_chunk=16, kv_chunk=16)
    kk = jnp.repeat(k, 2, 2)
    vv = jnp.repeat(v, 2, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
    a = binarize(s, 0.3, 4.0)
    mask = jnp.tril(jnp.ones((l, l), bool))
    a = jnp.where(mask[None, None], a, 0.0)
    want = jnp.einsum("bhqk,bkhd->bqhd", a, vv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_decode_attention_matches_naive_row():
    b, h, kh, d, s_len = 2, 8, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (b, s_len, kh, d))
    vc = jax.random.normal(ks[2], (b, s_len, kh, d))
    entry_pos = jnp.arange(s_len)
    out = nn.decode_attention(q, kc, vc, entry_pos=entry_pos,
                              cur_pos=jnp.asarray(20), window=8)
    want = naive(q, kc, vc, causal=True, window=8, q_offset=20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_fp32_accumulation_stability():
    # long context with bf16 inputs should not blow up
    q, k, v = _qkv(lq=16, lk=2048, h=2, kh=2, d=32, seed=11)
    out = nn.flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                             v.astype(jnp.bfloat16), causal=False,
                             q_chunk=16, kv_chunk=256)
    want = naive(q, k, v, causal=False)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=0.05, rtol=0.05)


def test_yarn_rope_matches_the_formula():
    """YaRN (arXiv:2309.00071) as HF transformers' _compute_yarn_parameters
    writes it, spelled out at a few positions for Mellum2's full layers:
    theta 500000, factor 16 over 8192 positions, beta 32 and 1, head 128;
    cos and sin scaled by the attention factor."""
    import math
    from repro.configs.base import YarnConfig
    yarn = YarnConfig(factor=16.0, original_max_position=8192,
                      beta_fast=32.0, beta_slow=1.0,
                      attention_factor=1.2772588722239782)
    d, theta = 128, 500000.0
    pos_freqs = theta ** (np.arange(0, d, 2) / d)
    extrapolation, interpolation = 1 / pos_freqs, 1 / (16.0 * pos_freqs)

    def dim(rot):
        return d * math.log(8192 / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    extrap_factor = 1 - ramp
    inv_freq = interpolation * (1 - extrap_factor) \
        + extrapolation * extrap_factor
    assert (low, high) == (18, 35)   # 18.08 and 34.98 before rounding
    np.testing.assert_allclose(nn.yarn_freqs(d, theta, yarn), inv_freq,
                               rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, d))
    positions = jnp.asarray([0, 1, 1000, 4095])
    got = nn.rope(x, positions, theta, yarn)
    # the angle in float32, as the program forms it
    ang = np.float32(np.asarray(positions)[:, None]) * np.float32(inv_freq)
    cos = (np.cos(ang) * yarn.attention_factor)[None, :, None, :]
    sin = (np.sin(ang) * yarn.attention_factor)[None, :, None, :]
    x1, x2 = np.asarray(x[..., :64]), np.asarray(x[..., 64:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # float32 cos and sin of angles up to 4095 rad are good to about an
    # ulp of the angle, 2.4e-4 rad, which |x| * 1.28 (under 5) scales
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=0)
    np.testing.assert_allclose(np.asarray(got[:, :2]), want[:, :2],
                               atol=1e-5, rtol=0)
    # position 0 only scales: the rope at angle 0 times the factor
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(x[:, 0]) * yarn.attention_factor,
                               rtol=1e-6)
