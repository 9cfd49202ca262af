"""Plain float32 reference of Mellum2-12B-A2.5B as the configuration file
states it, for one chip's share of its experts.

Written from the configuration file alone (its Hugging Face keys), in
straightforward ``jax.numpy`` at ``precision='highest'``, over a whole
sequence at once: no KV cache, no ring, no slots, no batching, no sorted
dispatch. It imports nothing of the program. It reads the program's
parameter tree by name: layer ``i`` is ``params["groups"][...][i // P, i
% P]`` with P the period of ``layer_types``. What it computes, for tokens
(S,):

- x = embedding rows.
- Each layer: h = RMSNorm(x); q, k, v = h W_q, h W_k, h W_v by heads; RoPE
  on q and k by absolute position, with the layer kind's entry of
  ``rope_parameters``; softmax(q k^T / sqrt(head_dim)) v per head, each
  key head shared by ``num_attention_heads / num_key_value_heads`` query
  heads, over the keys at or before the query and, on
  ``sliding_attention`` layers, with key > query - ``sliding_window``;
  x = x + ctx W_o. Then h = RMSNorm(x); router logits h W_r over all the
  model's experts; softmax; the top ``num_experts_per_tok``, renormalised
  to sum to 1 (``norm_topk_prob``); x = x + sum over the experts held of
  weight * (silu(h W_gate) * (h W_up)) W_down, where an expert's weight is
  0 for a token that did not pick it.
- Logits = RMSNorm(x) W_head.

RoPE rotates the two halves of a head: (x1, x2) -> (x1 cos - x2 sin,
x2 cos + x1 sin) at angle position * f_i. ``default``: f_i = theta^(-2i /
d). ``yarn`` (arXiv:2309.00071): with r(b) = d ln(original / (2 pi b)) /
(2 ln theta), low = max(floor(r(beta_fast)), 0) and high =
min(ceil(r(beta_slow)), d - 1), ramp_i = clip((i - low) / (high - low), 0,
1); f_i = ramp_i theta^(-2i/d) / factor + (1 - ramp_i) theta^(-2i/d); cos
and sin are multiplied by ``attention_factor``.

Departures from the published model: the experts held are ids [offset,
offset + held) of ``published_num_experts``, and what the others would
add is left out, as the program leaves it out (the chip's share of an
expert-parallel deployment); the MTP head the model card describes is
left out (the configuration has no keys for it); no QK-norm (the
configuration names none).

``compute`` names the precision: ``"float32"`` (the reference) or a lower
one, in which every matmul takes its operands and every activation and
residual sum is stored, as a program computing in that dtype would;
products still accumulate in float32. Attention is computed in blocks of
queries so that a 4096-position sequence fits beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench.reference.spikingformer import matmul, store

F32 = jnp.float32
QUERY_BLOCK = 512


def period(c) -> int:
    """Layers per repeat of ``layer_types``: the program's group."""
    return c["layer_types"].index("full_attention") + 1


def rope_freqs(c, kind: str):
    """(f (head_dim/2,), cos/sin scale) of ``rope_parameters[kind]``."""
    r = c["rope_parameters"][kind]
    d, theta = c["head_dim"], r["rope_theta"]
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if r["rope_type"] == "default":
        return base.astype(np.float32), 1.0

    def corr(b):
        return d * math.log(r["original_max_position_embeddings"]
                            / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(corr(r["beta_fast"])), 0)
    high = min(math.ceil(corr(r["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    f = ramp * base / r["factor"] + (1 - ramp) * base
    return f.astype(np.float32), r["attention_factor"]


def rope(c, x, positions, kind, compute):
    """x (S, H, hd) float32."""
    f, scale = rope_freqs(c, kind)
    ang = positions.astype(F32)[:, None] * jnp.asarray(f)      # (S, hd/2)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return store(jnp.concatenate([x1 * cos - x2 * sin,
                                  x2 * cos + x1 * sin], -1), compute)


def rmsnorm(c, x, scale, compute):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return store(x / jnp.sqrt(var + c["rms_norm_eps"]) * scale.astype(F32),
                 compute)


def attention(c, q, k, v, kind, compute):
    """q (S, H, hd), k and v (S, KH, hd) -> (S, H * hd), causal; on a
    sliding layer key > query - window. Blocks of QUERY_BLOCK queries."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kh, vh = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (H,hd,S) (H,S,hd)
    blk = min(QUERY_BLOCK, s)
    n = -(-s // blk)
    qb = jnp.pad(q, ((0, n * blk - s), (0, 0), (0, 0))).reshape(
        n, blk, h, hd)

    def one(args):
        i, qi = args
        qpos = i * blk + jnp.arange(blk)
        kpos = jnp.arange(s)
        scores = matmul(qi.transpose(1, 0, 2), kh) / math.sqrt(hd)
        ok = kpos[None, :] <= qpos[:, None]
        if kind == "sliding_attention":
            ok &= kpos[None, :] > qpos[:, None] - c["sliding_window"]
        scores = jnp.where(ok[None], scores, -jnp.inf)
        probs = store(jax.nn.softmax(scores, axis=-1), compute)
        return matmul(probs, vh, compute).transpose(1, 0, 2)  # (blk, H, hd)
    out = jax.lax.map(one, (jnp.arange(n), qb))
    return out.reshape(n * blk, h * hd)[:s]


def experts(c, p, x, offset, compute):
    """x (S, D) -> (S, D): the held experts' part of the layer."""
    logits = matmul(x, p["router"], compute)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    held = offset + jnp.arange(p["up"].shape[0])
    weight = jnp.where(idx[None] == held[:, None, None], w[None], 0.0).sum(
        -1)                                                   # (E, S)
    hid = store(jax.nn.silu(matmul(x[None], p["gate"], compute))
                * matmul(x[None], p["up"], compute), compute)  # (E, S, F)
    out = (weight[..., None] * matmul(hid, p["down"], compute)).sum(0)
    return store(out, compute)


def layer(c, p, x, positions, kind, offset, compute):
    s = x.shape[0]
    h, kh, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    n = rmsnorm(c, x, p["ln1"]["scale"], compute)
    q = matmul(n, p["wq"]["w"], compute).reshape(s, h, hd)
    k = matmul(n, p["wk"]["w"], compute).reshape(s, kh, hd)
    v = matmul(n, p["wv"]["w"], compute).reshape(s, kh, hd)
    q, k = rope(c, q, positions, kind, compute), \
        rope(c, k, positions, kind, compute)
    ctx = attention(c, q, k, v, kind, compute)
    x = store(x + matmul(ctx, p["wo"]["w"], compute), compute)
    n = rmsnorm(c, x, p["ln2"]["scale"], compute)
    return store(x + experts(c, p["moe"], n, offset, compute), compute)


def forward(c, params, tokens, compute="float32", offset=0):
    """tokens (S,) int32 -> logits (S, vocab) float32. The layers run one
    group (one period of ``layer_types``) at a time, each upcast to
    float32 only as it runs."""
    s = tokens.shape[0]
    x = store(params["embed"]["table"][tokens].astype(F32), compute)
    positions = jnp.arange(s)
    kinds = c["layer_types"][:period(c)]

    def group(x, gp):
        for j, kind in enumerate(kinds):
            p = jax.tree_util.tree_map(lambda a: a[j].astype(F32), gp)
            x = layer(c, p, x, positions, kind, offset, compute)
        return x, None
    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(group, x, params["groups"])
        x = rmsnorm(c, x, params["final_norm"]["scale"], compute)
        return matmul(x, params["lm_head"]["w"], compute)
