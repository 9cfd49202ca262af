"""Plain float32 reference of the spiking causal LM (spikingformer-lm).

Written from the configuration file alone, in straightforward
``jax.numpy`` at ``precision='highest'``, over a whole sequence at once:
no KV cache, no packing, no chunking, no slots. It imports nothing of the
program. What it computes, for tokens (S,):

- x = embedding rows, repeated over the T time steps: (T, S, D).
- Each layer: h = RMSNorm(x); q, k, v = h W_q, h W_k, h W_v; RoPE on q and
  k by absolute position; q, k, v = LIF over T; scores = q k^T /
  sqrt(d_head) per head; a = 1[scores - delta >= 0] on and below the
  diagonal, 0 above; x = x + (a v) W_o; x = x + LIF(RMSNorm(x) W_up)
  W_down.
- Logits = RMSNorm(mean of x over T) W_head.

RoPE rotates the two halves of a head: with f_i = theta^(-i / (d/2)),
(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) at angle position * f_i.
LIF: u = (1 - 1/tau) u + input; spike = 1[u - v_th >= 0]; hard reset.

``compute`` names the precision: ``"float32"`` (the reference) or a lower
one, in which every matmul takes its operands and every activation,
membrane and residual sum is stored, as a program computing in that dtype
would; products still accumulate in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chip_bench.reference.spikingformer import lif, matmul, store

F32 = jnp.float32


def rmsnorm(c, x, scale, compute="float32"):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return store(x / jnp.sqrt(var + c["norm_eps"]) * scale.astype(F32),
                 compute)


def rope(c, x, positions, compute="float32"):
    """x (..., S, H, hd) float32."""
    half = x.shape[-1] // 2
    freqs = c["rope_theta"] ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return store(jnp.concatenate([x1 * cos - x2 * sin,
                                  x2 * cos + x1 * sin], -1), compute)


def layer(c, p, x, positions, compute):
    t, s, d = x.shape
    h, kh, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    n = rmsnorm(c, x, p["ln1"]["scale"], compute)
    q = matmul(n, p["wq"]["w"], compute).reshape(t, s, h, hd)
    k = matmul(n, p["wk"]["w"], compute).reshape(t, s, kh, hd)
    v = matmul(n, p["wv"]["w"], compute).reshape(t, s, kh, hd)
    q, k = rope(c, q, positions, compute), rope(c, k, positions, compute)
    q, k, v = (lif(c, u, compute) for u in (q, k, v))
    rep = h // kh
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    heads = lambda u: u.transpose(0, 2, 1, 3)              # (T, H, S, hd)
    scores = matmul(heads(q), heads(k).swapaxes(-1, -2)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    attn = jnp.where(causal, (scores - p["delta"] >= 0).astype(F32), 0.0)
    ctx = matmul(attn, heads(v), compute).transpose(0, 2, 1, 3).reshape(
        t, s, h * hd)
    x = store(x + matmul(ctx, p["wo"]["w"], compute), compute)
    up = matmul(rmsnorm(c, x, p["ln2"]["scale"], compute),
                p["mlp"]["up"]["w"], compute)
    hid = lif(c, up, compute)
    x = store(x + matmul(hid, p["mlp"]["down"]["w"], compute), compute)
    return x, [q.mean(), k.mean(), v.mean(), hid.mean()]


def forward(c, params, tokens, compute="float32"):
    """tokens (S,) int32 -> (logits (S, vocab) float32, densities)."""
    s = tokens.shape[0]
    x = store(params["embed"]["table"][tokens].astype(F32), compute)
    x = jnp.broadcast_to(x[None], (c["time_steps"], s, x.shape[-1]))
    positions = jnp.arange(s)
    densities = []
    for i in range(c["num_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x, dens = layer(c, p, x, positions, compute)
        densities += dens
    x = rmsnorm(c, store(x.mean(axis=0), compute),
                params["final_norm"]["scale"], compute)
    return matmul(x, params["lm_head"]["w"], compute), jnp.stack(densities)


def density_names(c):
    return [f"layer{i}.{n}" for i in range(c["num_layers"])
            for n in ("q", "k", "v", "mlp")]
