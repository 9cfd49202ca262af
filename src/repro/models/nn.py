"""Minimal functional NN layer library (pure JAX pytrees, no flax).

Conventions:
  * params are nested dicts of jnp arrays, created by ``*_init`` functions;
  * activations default to the model compute dtype (bf16), matmuls accumulate
    in fp32 via ``preferred_element_type`` then cast back;
  * attention is *chunked flash-style in pure jnp* (no L x L materialization)
    so 32k/500k shapes lower with bounded live memory; the Pallas kernels in
    ``repro.kernels`` replace the binary-attention inner loop on TPU.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import constrain

# ---------------------------------------------------------------------------
# Initializers / basic layers
# ---------------------------------------------------------------------------


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                std: Optional[float] = None, dtype=jnp.bfloat16):
    std = (1.0 / math.sqrt(d_in)) if std is None else std
    p = {"w": normal(key, (d_in, d_out), std, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x, *, spikes: bool = False, counts: bool = False):
    # ``spikes=True`` marks the input as a {0,1} spike tensor (or, with
    # ``counts=True``, the sparse integer counts binary attention emits):
    # those call sites route through the dual-engine dispatch
    # (core/engine.py), which may run the occupancy-skipping sparse
    # kernel when an engine is installed. With no ambient engine this is
    # the plain dense path. Quantized param dicts ({'qw','scale'[,'b']},
    # repro.quant) dispatch transparently: spike inputs take the
    # int8-accumulating engine path (counts ride int32 lanes — int8
    # would wrap at 128), analog inputs the weight-only dequantizing
    # reference.
    if "qw" in p:
        from repro.core import engine as _engine  # lazy: no import cycle
        if spikes and _engine.get_engine() is not None:
            return _engine.spike_linear(p, x, counts=counts)
        return _engine.dense_quant_linear(p, x)
    if spikes:
        from repro.core import engine as _engine  # lazy: no import cycle
        if _engine.get_engine() is not None:
            return _engine.spike_linear(p, x)
    # emit in the activation dtype: the MXU accumulates fp32 internally,
    # and a bf16 result keeps every downstream collective (row-parallel
    # psum, FSDP gather of the transposed weight in bwd) in bf16 instead
    # of letting XLA hoist an f32 convert before them (§Perf F1: halved
    # the dominant all-reduces on all three hillclimb cells).
    y = jnp.dot(x, p["w"], preferred_element_type=x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(d: int, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def groupnorm(p, x, groups: int, eps: float = 1e-5):
    """GroupNorm over the last dim split into ``groups`` (RWKV head-norm)."""
    d = x.shape[-1]
    x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], groups, d // groups)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = ((x32 - mu) * jax.lax.rsqrt(var + eps)).reshape(*x.shape[:-1], d)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def embedding_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return {"table": normal(key, (vocab, d), 1.0 / math.sqrt(d), dtype)}


def embed(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def unembed(p, x):
    return jnp.dot(x, p["table"].T.astype(x.dtype),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# BatchNorm with running stats (Spikingformer / CIFAR-Net use conv+BN)
# ---------------------------------------------------------------------------


def batchnorm_init(d: int, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def batchnorm_state_init(d: int):
    return {"mean": jnp.zeros((d,), jnp.float32),
            "var": jnp.ones((d,), jnp.float32)}


def batchnorm(p, state, x, *, train: bool, momentum: float = 0.9,
              eps: float = 1e-5):
    """BN over all leading axes; returns (y, new_state)."""
    x32 = x.astype(jnp.float32)
    if train:
        axes = tuple(range(x.ndim - 1))
        mu = jnp.mean(x32, axis=axes)
        var = jnp.var(x32, axis=axes)
        new_state = {"mean": momentum * state["mean"] + (1 - momentum) * mu,
                     "var": momentum * state["var"] + (1 - momentum) * var}
    else:
        mu, var = state["mean"], state["var"]
        new_state = state
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if name == "relu2":  # squared ReLU (Nemotron-4)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


def mlp_init(key, d_model: int, d_ff: int, *, gated: bool, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    p = {"up": linear_init(ks[0], d_model, d_ff, dtype=dtype),
         "down": linear_init(ks[1], d_ff, d_model, dtype=dtype)}
    if gated:
        p["gate"] = linear_init(ks[2], d_model, d_ff, dtype=dtype)
    return p


def mlp(p, x, act: str):
    h = linear(p["up"], x)
    if "gate" in p:
        h = activation(act)(linear(p["gate"], x)) * h
    else:
        h = activation(act)(h)
    h = constrain(h, "batch", "seq", "d_ff")
    return linear(p["down"], h)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def yarn_freqs(d: int, theta: float, yarn) -> np.ndarray:
    """YaRN's (arXiv:2309.00071) rotary frequencies for head size ``d``,
    as HF transformers' ``_compute_yarn_parameters`` computes them: the
    dims that turn fewer than ``beta_slow`` times over the original
    context are interpolated (frequency / factor), those that turn more
    than ``beta_fast`` times keep their frequency, and a linear ramp
    between the two correction dims blends the rest."""
    half = d // 2
    base = theta ** (np.arange(0, d, 2, dtype=np.float32) / d)

    def correction_dim(rotations):
        return d * math.log(yarn.original_max_position
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)                 # share of the interpolated one
    return (ramp / (yarn.factor * base) + (1 - ramp) / base).astype(
        np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn=None) -> jax.Array:
    """Apply RoPE. x: (B, L, H, D), positions: (B, L) or (L,). ``yarn``
    (``configs.base.YarnConfig``): YaRN's frequencies, and cos and sin
    scaled by its attention factor."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_freqs(d, theta, yarn))
    if positions.ndim == 1:
        positions = positions[None]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, L, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — pure jnp, no L x L materialization
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    q_offset=0,
                    kv_valid_len: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    q_chunk: int = 1024,
                    kv_chunk: int = 2048) -> jax.Array:
    """Online-softmax attention with GQA broadcast.

    q: (B, Lq, H, D); k, v: (B, Lk, KH, D) with H % KH == 0.
    ``q_offset``: absolute position of q[0] (decode: cur_len - Lq).
    ``kv_valid_len``: mask out cache positions >= this (scalar or (B,)).
    ``window``: sliding-window attention width (None = full).
    """
    b, lq, h, d = q.shape
    _, lk, kh, _ = k.shape
    rep = h // kh
    scale = (1.0 / math.sqrt(d)) if scale is None else scale

    q_chunk = min(q_chunk, lq)
    kv_chunk = min(kv_chunk, lk)
    nq = -(-lq // q_chunk)
    nk = -(-lk // kv_chunk)

    qp = _pad_to(q, nq * q_chunk, 1).reshape(b, nq, q_chunk, h, d)
    kp = _pad_to(k, nk * kv_chunk, 1).reshape(b, nk, kv_chunk, kh, d)
    vp = _pad_to(v, nk * kv_chunk, 1).reshape(b, nk, kv_chunk, kh, d)
    # group query heads onto kv heads: (B, nq, qc, KH, rep, D)
    qp = qp.reshape(b, nq, q_chunk, kh, rep, d)

    q_pos_base = jnp.asarray(q_offset)
    kvl = None if kv_valid_len is None else jnp.asarray(kv_valid_len)

    # vmap over batch, scan over q chunks, inner scan over kv chunks
    def per_batch(q_b, k_b, v_b):
        def q_scan_body(_, inp):
            qi, q_blk = inp
            qpos = q_pos_base + qi * q_chunk + jnp.arange(q_chunk)

            def kv_body(carry, kv_inp):
                m, l, acc = carry
                ki, k_blk, v_blk = kv_inp
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.einsum("qgrd,kgd->qgrk", q_blk, k_blk,
                               preferred_element_type=jnp.float32) * scale
                mask = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    mask &= kpos[None, :] <= qpos[:, None]
                if window is not None:
                    mask &= kpos[None, :] > qpos[:, None] - window
                mask &= (kpos < lk)[None, :]
                if kvl is not None:
                    mask &= (kpos < kvl)[None, :]
                s = jnp.where(mask[:, None, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "qgrk,kgd->qgrd", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((q_chunk, kh, rep), NEG_INF, jnp.float32)
            l0 = jnp.zeros((q_chunk, kh, rep), jnp.float32)
            a0 = jnp.zeros((q_chunk, kh, rep, d), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                          (jnp.arange(nk), k_b, v_b))
            out = acc / jnp.maximum(l[..., None], 1e-20)
            return None, out.astype(q.dtype)

        _, outs = jax.lax.scan(q_scan_body, None,
                               (jnp.arange(nq), q_b))
        return outs  # (nq, qc, KH, rep, D)

    outs = jax.vmap(per_batch)(qp, kp, vp)
    out = outs.reshape(b, nq * q_chunk, h, d)[:, :lq]
    return out.astype(q.dtype)


def banded_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           window: int,
                           scale: Optional[float] = None,
                           q_chunk: int = 512) -> jax.Array:
    """Sliding-window attention with *statically banded* compute.

    For each q chunk only the kv band ``[q_start - window, q_end]`` is
    touched (one dynamic_slice), so HLO FLOPs scale as O(L * window) instead
    of O(L^2) — this is what makes gemma3 local layers and SWA prefill at
    32k/500k roofline-sane. Causal by construction. Self-attention only
    (Lq == Lk, offset 0).
    """
    b, l, h, d = q.shape
    _, lk, kh, _ = k.shape
    assert l == lk, "banded attention is for self-attention prefill"
    rep = h // kh
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    q_chunk = min(q_chunk, l)
    nq = -(-l // q_chunk)
    lpad = nq * q_chunk
    band = min(lk, window + q_chunk)  # static band length

    qp = _pad_to(q, lpad, 1).reshape(b, nq, q_chunk, kh, rep, d)
    kp = _pad_to(k, lpad, 1)
    vp = _pad_to(v, lpad, 1)

    def per_batch(q_b, k_b, v_b):
        def q_body(_, inp):
            qi, q_blk = inp
            q_start = qi * q_chunk
            band_start = jnp.clip(q_start + q_chunk - band, 0, lpad - band)
            k_band = jax.lax.dynamic_slice_in_dim(k_b, band_start, band, 0)
            v_band = jax.lax.dynamic_slice_in_dim(v_b, band_start, band, 0)
            qpos = q_start + jnp.arange(q_chunk)
            kpos = band_start + jnp.arange(band)
            s = jnp.einsum("qgrd,kgd->qgrk", q_blk, k_band,
                           preferred_element_type=jnp.float32) * scale
            mask = (kpos[None, :] <= qpos[:, None])
            mask &= kpos[None, :] > qpos[:, None] - window
            mask &= (kpos < l)[None, :]
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
            m = s.max(-1, keepdims=True)
            p = jnp.exp(s - m)
            out = jnp.einsum("qgrk,kgd->qgrd", p.astype(v_band.dtype),
                             v_band, preferred_element_type=jnp.float32)
            out = out / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
            return None, out.astype(q.dtype)

        _, outs = jax.lax.scan(q_body, None,
                               (jnp.arange(nq),
                                q_b))
        return outs

    outs = jax.vmap(per_batch)(qp, kp, vp)
    return outs.reshape(b, lpad, h, d)[:, :l].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     entry_pos: jax.Array, cur_pos: jax.Array,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """Attention for a short query span against a (possibly rolling) KV
    cache — one decode token or a chunked-prefill bite.

    q: (B, Lq, H, D); k_cache/v_cache: (B, S, KH, D);
    entry_pos: (S,) or (B, S) absolute position of each cache entry (-1 =
    empty); cur_pos: absolute position of each query — scalar (all rows,
    Lq == 1), (B,) per-row first-query position, or (B, Lq) explicit.
    Causality comes entirely from the entry_pos <= query-position mask, so
    per-row positions give every batch row its own timeline.
    """
    b, lq, h, d = q.shape
    _, s_len, kh, _ = k_cache.shape
    rep = h // kh
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if entry_pos.ndim == 1:
        entry_pos = entry_pos[None]
    qpos = jnp.asarray(cur_pos)
    if qpos.ndim == 0:
        qpos = qpos[None, None]
    elif qpos.ndim == 1:
        qpos = qpos[:, None] + jnp.arange(lq)
    qpos = jnp.broadcast_to(qpos, (b, lq))
    qf = q.reshape(b, lq, kh, rep, d).astype(jnp.float32)
    sc = jnp.einsum("bqgrd,bkgd->bqgrk", qf,
                    k_cache.astype(jnp.float32)) * scale
    valid = (entry_pos[:, None, :] >= 0) & \
        (entry_pos[:, None, :] <= qpos[:, :, None])
    if window is not None:
        valid &= entry_pos[:, None, :] > qpos[:, :, None] - window
    sc = jnp.where(valid[:, :, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bqgrk,bkgd->bqgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, lq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Convolutions (Spikingformer SPS / CIFAR-Net)
# ---------------------------------------------------------------------------


def conv2d_init(key, c_in: int, c_out: int, ksize: int = 3,
                dtype=jnp.bfloat16):
    std = 1.0 / math.sqrt(c_in * ksize * ksize)
    return {"w": normal(key, (ksize, ksize, c_in, c_out), std, dtype)}


def conv2d(p, x, stride: int = 1, padding: str = "SAME"):
    """x: (B, H, W, C) NHWC."""
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), p["w"].astype(jnp.float32),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y.astype(x.dtype)


def maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def causal_depthwise_conv1d(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (B, L, C); w: (K, C) depthwise causal conv (mamba front conv)."""
    k = w.shape[0]
    xpad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xpad[:, i:i + x.shape[1]].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def sinusoid_positions(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def binary_flash_attention(q, k, v, *, delta, alpha: float,
                           causal: bool = True,
                           window: Optional[int] = None,
                           q_offset=0,
                           kv_valid_len: Optional[jax.Array] = None,
                           scale: Optional[float] = None,
                           binarize_scores: bool = True,
                           q_chunk: int = 1024,
                           kv_chunk: int = 2048) -> jax.Array:
    """Chunked *binary* attention (no softmax => single exact pass).

    scores = (Q @ K^T) * scale; attn = 1[scores > delta]; out = attn @ V.
    This is the pure-jnp reference dataflow of the binary engine; the Pallas
    kernel (kernels/spike_attention) implements the same contract.
    """
    from repro.core.spiking import binarize
    b, lq, h, d = q.shape
    _, lk, kh, _ = k.shape
    rep = h // kh
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    q_chunk = min(q_chunk, lq)
    kv_chunk = min(kv_chunk, lk)
    nq = -(-lq // q_chunk)
    nk = -(-lk // kv_chunk)

    qp = _pad_to(q, nq * q_chunk, 1).reshape(b, nq, q_chunk, kh, rep, d)
    kp = _pad_to(k, nk * kv_chunk, 1).reshape(b, nk, kv_chunk, kh, d)
    vp = _pad_to(v, nk * kv_chunk, 1).reshape(b, nk, kv_chunk, kh, d)
    kvl = None if kv_valid_len is None else jnp.asarray(kv_valid_len)
    q_pos_base = jnp.asarray(q_offset)

    def per_batch(q_b, k_b, v_b):
        def q_body(_, inp):
            qi, q_blk = inp
            qpos = q_pos_base + qi * q_chunk + jnp.arange(q_chunk)

            def kv_body(acc, kv_inp):
                ki, k_blk, v_blk = kv_inp
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.einsum("qgrd,kgd->qgrk", q_blk, k_blk,
                               preferred_element_type=jnp.float32) * scale
                if binarize_scores:
                    a = binarize(s, jnp.asarray(delta, jnp.float32), alpha)
                else:
                    a = s
                mask = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    mask &= kpos[None, :] <= qpos[:, None]
                if window is not None:
                    mask &= kpos[None, :] > qpos[:, None] - window
                mask &= (kpos < lk)[None, :]
                if kvl is not None:
                    mask &= (kpos < kvl)[None, :]
                a = jnp.where(mask[:, None, None, :], a, 0.0)
                acc = acc + jnp.einsum("qgrk,kgd->qgrd",
                                       a.astype(v_blk.dtype), v_blk,
                                       preferred_element_type=jnp.float32)
                return acc, None

            a0 = jnp.zeros((q_chunk, kh, rep, d), jnp.float32)
            acc, _ = jax.lax.scan(kv_body, a0, (jnp.arange(nk), k_b, v_b))
            return None, acc.astype(q.dtype)

        _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), q_b))
        return outs

    outs = jax.vmap(per_batch)(qp, kp, vp)
    out = outs.reshape(b, nq * q_chunk, h, d)[:, :lq]
    return out.astype(q.dtype)
