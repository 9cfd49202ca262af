"""Jit'd public wrappers around the Pallas kernels.

``binary_attention`` / ``spike_attention`` carry a custom VJP: the forward
runs a Pallas kernel (the fused MXU pass, or the bit-packed AND-PopCount
score stage); the backward recomputes through the pure-jnp oracle with
surrogate gradients (standard recompute-in-bwd pattern — the L x L
attention matrix still never persists between fwd and bwd).
``lif_one_pass`` runs its kernel only where nothing differentiates it:
under differentiation it is the scan, with the scan's own VJP.

On non-TPU backends kernels run in ``interpret=True`` mode (bit-exact
Python execution of the kernel body) — that is how this CPU container
validates them; on TPU the same calls compile to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bitpack import pack_bits
from repro.core.scopes import annotate
from repro.core.spiking import SpikingConfig, binarize, lif_lax_scan
from repro.parallel.sharding import get_rules
from . import ref
from .lif import lif_forward as _lif_pallas
from .popcount_attention import popcount_scores as _popcount_pallas
from .spike_attention import spike_attention as _attn_pallas
from .spike_matmul import spike_matmul as _matmul_pallas
from .spike_matmul import spike_matmul_batched as _matmul_batched_pallas


# ---------------------------------------------------------------------------
# binary attention (fwd: Pallas, bwd: surrogate-gradient recompute)
# ---------------------------------------------------------------------------
#
# The differentiable core works on the *folded* (BH, L, D) layout — the
# layout the binary-engine kernels consume. Dispatch callers (core/
# attention.py) fold their leading dims themselves; the model-layout
# (B', L, H, D) wrapper below keeps the historical entry point.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _binary_attention(q, k, v, delta, alpha, scale, causal, binarize_scores,
                      use_popcount, block_q, block_k):
    if use_popcount:
        # faithful FPGA port: bit-pack the spikes, AND-PopCount the score
        # stage on the VPU, context stage as a jnp matmul on the exact
        # integer counts. Bit-identical to the MXU kernel: {0,1} dots in
        # fp32 ARE the popcounts, and the threshold compare is the same
        # expression.
        counts = _popcount_pallas(pack_bits(q), pack_bits(k),
                                  block_q=block_q, block_k=block_k)
        s = counts.astype(jnp.float32) * scale
        if binarize_scores:
            a = (s - delta >= 0).astype(jnp.float32)
        else:
            a = s
        if causal:
            lq, lk = a.shape[-2:]
            mask = jnp.tril(jnp.ones((lq, lk), bool))
            a = jnp.where(mask[None], a, 0.0)
        out = jnp.einsum("bqk,bkd->bqd", a, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)
    return _attn_pallas(q, k, v, scale=scale, delta=delta, causal=causal,
                        binarize_scores=binarize_scores,
                        block_q=block_q, block_k=block_k)


def _binary_fwd(q, k, v, delta, alpha, scale, causal, binarize_scores,
                use_popcount, block_q, block_k):
    out = _binary_attention(q, k, v, delta, alpha, scale, causal,
                            binarize_scores, use_popcount, block_q, block_k)
    return out, (q, k, v, delta, alpha)


def _jnp_folded(q, k, v, delta, alpha, scale, causal, binarize_scores):
    """Pure-jnp surrogate-gradient oracle on the folded (BH, L, D) layout."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    a = binarize(s, delta, alpha) if binarize_scores else s
    if causal:
        l = q.shape[1]
        mask = jnp.tril(jnp.ones((l, l), bool))
        a = jnp.where(mask[None], a, 0.0)
    out = jnp.einsum("bqk,bkd->bqd", a, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _binary_bwd(scale, causal, binarize_scores, use_popcount, block_q,
                block_k, res, g):
    q, k, v, delta, alpha = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, d_: _jnp_folded(q_, k_, v_, d_, alpha, scale,
                                           causal, binarize_scores),
        q, k, v, delta)
    dq, dk, dv, dd = vjp(g)
    return dq, dk, dv, dd, None


_binary_attention.defvjp(_binary_fwd, _binary_bwd)


def binary_attention(q, k, v, *, scale: float, delta, alpha: float = 4.0,
                     causal: bool = False, binarize_scores: bool = True,
                     use_popcount: bool = False,
                     block_q: int = 128, block_k: int = 128):
    """Folded-layout binary attention: q/k/v (BH, L, D) spike tensors.

    Forward runs the fused MXU Pallas kernel (``use_popcount=False``) or
    the bit-packed AND-PopCount score kernel (``use_popcount=True``);
    backward recomputes with surrogate gradients. This is the entry the
    binary-engine dispatch (core/engine.resolve_binary_mode) targets.
    """
    delta = jnp.asarray(delta, jnp.float32)
    return _binary_attention(q, k, v, delta, alpha, scale, causal,
                             binarize_scores, use_popcount,
                             block_q, block_k)


def spike_attention(q, k, v, *, scale: float, delta, alpha: float = 4.0,
                    causal: bool = False, binarize_scores: bool = True):
    """Model-layout fused binary attention: q/k/v (B', L, H, D)."""
    b, l, h, d = q.shape
    fold = lambda u: u.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    out = binary_attention(fold(q), fold(k), fold(v), scale=scale,
                           delta=delta, alpha=alpha, causal=causal,
                           binarize_scores=binarize_scores)
    return out.reshape(b, h, l, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# sparse spike matmul
# ---------------------------------------------------------------------------

def spike_matmul(s, w, *, bias=None, block_m: int = 128, block_n: int = 128,
                 block_k: int = 128):
    """y = s @ w (+ bias) with zero-block skipping. s: (M, K) spikes,
    w: (K, N). Non-divisible shapes are zero-padded internally."""
    return _matmul_pallas(s, w, bias=bias, block_m=block_m, block_n=block_n,
                          block_k=block_k)


def spike_matmul_batched(s, w, *, bias=None, block_m: int = 128,
                         block_n: int = 128, block_k: int = 128):
    """Batched y = s @ w (+ bias): s (T, B, ..., K) spikes folded into M.

    For a differentiable, config-driven entry use
    ``repro.core.engine.spike_linear`` — this wrapper is the raw fwd-only
    kernel call."""
    return _matmul_batched_pallas(s, w, bias=bias, block_m=block_m,
                                  block_n=block_n, block_k=block_k)


# ---------------------------------------------------------------------------
# LIF
# ---------------------------------------------------------------------------

def lif(currents, cfg: SpikingConfig, v0=None):
    """LIF over (T, ...) as ``core.spiking.lif_scan`` runs it: (spikes,
    final membrane). Where ``_one_pass`` holds, ``lif_one_pass``; else
    ``lif_lax_scan``."""
    if _one_pass(v0):
        return lif_one_pass(currents, cfg)
    return lif_lax_scan(currents, cfg, v0)


def _one_pass(v0) -> bool:
    """Whether the LIF takes the one-pass kernel: on the chip, from a zero
    membrane, and not while a mesh partitions the program (sharding rules
    installed): XLA cannot partition a Mosaic kernel.

    No size bound: one bf16 call at T 4 on a v5e took 0.42 us one pass
    against the scan's 1.68 at (4, 1, 256), 0.66 / 3.83 at (4, 4, 256),
    2.91 / 7.31 at (4, 256, 256) and 11.2 / 17.2 at (4, 1024, 256); the
    kernel won at every size measured, and 256 neurons a step (a
    one-slot decode of spikingformer-lm) is the smallest LIF in the repo.
    Those times predate the kernel's cut of R and D (see PERF.md)."""
    return (jax.default_backend() == "tpu" and get_rules() is None
            and v0 is None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def lif_one_pass(currents, cfg: SpikingConfig):
    """LIF over (T, ...) from a zero membrane: the spikes from the one-pass
    kernel (scope ``lif.kernel``), the final membrane from the scan, which
    XLA removes where the caller drops it."""
    with annotate("lif.kernel"):
        spikes = _lif_pallas(currents, decay=cfg.decay, v_th=cfg.v_threshold,
                             soft_reset=cfg.soft_reset)
    return spikes, lif_lax_scan(currents, cfg)[1]


def _lif_one_pass_fwd(currents, cfg):
    """Under differentiation the scan runs, and its pullback is the
    residual: training computes what it did before the kernel."""
    return jax.vjp(lambda c: lif_lax_scan(c, cfg), currents)


def _lif_one_pass_bwd(cfg, pullback, cts):
    return pullback(cts)


lif_one_pass.defvjp(_lif_one_pass_fwd, _lif_one_pass_bwd)


# ---------------------------------------------------------------------------
# bit-packed popcount scores
# ---------------------------------------------------------------------------

def popcount_attention_scores(q_spikes, k_spikes):
    """q/k (BH, L, D) {0,1} -> int32 (BH, Lq, Lk) via pack + AND-popcount."""
    return _popcount_pallas(pack_bits(q_spikes), pack_bits(k_spikes))
