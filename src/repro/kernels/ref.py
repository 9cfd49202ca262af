"""Pure-jnp oracles for every Pallas kernel (the "ref.py" contract).

These define bit-exact semantics the kernels must match (tests sweep shapes
and dtypes against them with assert_allclose). The LIF kernel's oracle is
``core.spiking.lif_loop_reference``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def spike_attention_ref(q, k, v, *, scale: float, delta, causal: bool,
                        binarize_scores: bool = True):
    """Fused binary attention oracle.

    q, k, v: (B, H, L, D) spike tensors ({0,1} values, float dtype).
    scores = (q @ k^T) * scale; attn = spike(scores - delta); out = attn @ v.
    No softmax (spiking attention, paper Eq. 2 + binary attention [17]).
    The threshold compare is ``(s - delta) >= 0`` — the exact expression
    of ``core.spiking.binarize`` — so all engine modes agree on ties.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if binarize_scores:
        a = (s - delta >= 0).astype(jnp.float32)
    else:
        a = s
    if causal:
        l = q.shape[2]
        mask = jnp.tril(jnp.ones((l, l), bool))
        a = jnp.where(mask[None, None], a, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", a, v.astype(jnp.float32))
    return out.astype(q.dtype)


def spike_matmul_ref(s, w):
    """Sparse-engine oracle: y = s @ w with s a {0,1} spike matrix.

    s: (M, K) spikes; w: (K, N) weights. fp32 accumulation.
    """
    return jnp.dot(s.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(w.dtype)


def popcount_scores_ref(q_packed, k_packed):
    """AND-PopCount oracle on bit-packed spikes.

    q_packed: (B, H, Lq, W) uint32; k_packed: (B, H, Lk, W) uint32.
    Returns (B, H, Lq, Lk) int32 overlap counts.
    """
    anded = q_packed[..., :, None, :] & k_packed[..., None, :, :]
    return jax.lax.population_count(anded).sum(axis=-1).astype(jnp.int32)
