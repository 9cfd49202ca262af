"""Fused SSA layer step — both overlay engines in one pipelined kernel.

The paper's headline schedule (Fig. 5, Section III-C) runs the sparse
engine and the binary engine *concurrently*: while the binary engine
computes ``QK^T_h`` / ``QK^T V_h`` for head *h*, the sparse engine is
already projecting Q/K/V for head *h+1*. The sequential reproduction
(``models/spikingformer._ssa``: four ``linear`` calls, then attention)
never overlaps anything; this kernel makes the overlap structural.

Grid ``(B, H, 4)``: for every (batch, head) pair, three sparse-engine
phases (Q/K/V projection tiles — per-time-step spike x weight dots with
an occupancy skip, plus the projection epilogue: BN affine + LIF for the
vision family, RoPE + LIF for the token family) followed by one
binary-engine phase (AND-PopCount score + value tiles). Adjacent grid
steps ``(b, h, attend)`` -> ``(b, h+1, project-Q)`` are exactly the
Fig. 5 adjacency: on TPU, Pallas's pipelined grid prefetches head
h+1's weight block while head h's attention tiles occupy the MXU, and
the batch row's (T, L, K) spike block is double-buffered by the same
pipeline (the BRAM double-buffer of the overlay, DESIGN.md §10). Q/K/V
spike trains persist across the four phases in VMEM scratch — the L x
d_head attention operands never leave the chip.

Bit-exactness (DESIGN.md §4 contract): every projection contracts the
*full* K dim in one fp32-accumulated dot (no K tiling — term-for-term
the dense reference), the epilogues repeat the reference expressions
(``nn.batchnorm`` eval affine, ``nn.rope``, ``core.spiking.lif_step``)
on identical dtypes, and the attention phase is the integer-exact
binary dataflow. ``reference_bundle`` below is the sequential oracle
the kernel is pinned against bitwise — and the recompute target of the
fused path's custom VJP (``core.engine``).

Measurement (the "measured, not modeled" hidden fraction): the kernel
counts *executed* compute sub-steps per (head, phase) — an all-dark
spike slab skips its dot via ``lax.cond`` and is not counted — into an
``(H, 4)`` int32 side output. ``core.dual_engine.fused_step_metrics``
feeds those counts to the Fig. 5 event schedule, so the bench's
``hidden_fraction`` derives from the kernel's actual execution, not
from the analytic MAC model. Counts are data-deterministic, so CI gates
them (``benchmarks/check_regression.py``).

Per-head weight, scale and epilogue operands are laid out head-major,
so every block's two minor dims are whole array dims (Mosaic's (8, 128)
rule at head_dim 32 or 64); the counts and the score threshold live in
SMEM. ``tests/test_tpu_compile.py`` compiles it for a v5e.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scopes import annotate
from repro.core.spiking import SpikingConfig, binarize, lif_scan

FAMILIES = ("bn", "rope")
PHASES = ("q", "k", "v", "attend")


def _kernel(x_ref, w_ref, scale_ref, aux_ref, delta_ref, o_ref, cnt_ref,
            qs, ks, vs, *, family, t_steps, l, k_dim, head_dim,
            scale, causal, binarize_scores, decay, v_th, soft_reset, eps,
            has_scale, dtype):
    b, h, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    half = head_dim // 2

    @pl.when((b == 0) & (p == 0))
    def _init_counts():
        for col in range(4):
            cnt_ref[h * 4 + col] = jnp.int32(0)

    def project(dst, col, roped):
        w = w_ref[0, 0]                              # (K, hd)
        nexec = jnp.int32(0)
        vals = []
        for t in range(t_steps):
            slab = x_ref[0, t]                       # (L, K)
            occ = jnp.any(slab != 0)
            # occupancy skip: a dark slab contributes exact fp32 zeros,
            # so skipping its dot is bitwise-free — and *measured*: only
            # executed dots reach the counts output.
            acc = jax.lax.cond(
                occ,
                lambda s=slab: jax.lax.dot_general(
                    s, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32),
                lambda: jnp.zeros((l, head_dim), jnp.float32))
            nexec += occ.astype(jnp.int32)
            vals.append(acc)
        cur = jnp.stack(vals)                        # (T, L, hd) fp32
        if has_scale:
            # quantized codes: per-output-channel scale in the epilogue,
            # exactly dense_quant_linear's expression order
            cur = cur * scale_ref[0, 0].astype(jnp.float32)
        y = cur.astype(dtype)                        # linear emits act dtype
        if family == "bn":
            rows = aux_ref[0, 0]                     # (4, hd)
            mean, var = rows[0:1], rows[1:2]
            sc, bi = rows[2:3], rows[3:4]
            y32 = y.astype(jnp.float32)
            y32 = (y32 - mean) * jax.lax.rsqrt(var + eps)
            y32 = y32 * sc + bi                      # nn.batchnorm (eval)
            y = y32.astype(dtype)
        elif roped:                                  # rope family: q, k only
            cos = aux_ref[0][None]                   # (1, L, half)
            sin = aux_ref[1][None]
            x1 = y[..., :half].astype(jnp.float32)
            x2 = y[..., half:].astype(jnp.float32)
            y = jnp.concatenate([x1 * cos - x2 * sin,
                                 x2 * cos + x1 * sin], -1).astype(dtype)
        # LIF over the time axis (core.spiking.lif_step semantics)
        u = jnp.zeros((l, head_dim), dtype)
        for t in range(t_steps):
            u = decay * u + y[t]
            s_t = (u - v_th >= 0).astype(dtype)
            u = u - s_t * v_th if soft_reset else u * (1.0 - s_t)
            dst[t] = s_t
        cnt_ref[h * 4 + col] += nexec

    @pl.when(p == 0)
    def _q():
        project(qs, 0, roped=True)

    @pl.when(p == 1)
    def _k():
        project(ks, 1, roped=True)

    @pl.when(p == 2)
    def _v():
        project(vs, 2, roped=False)

    @pl.when(p == 3)
    def _attend():
        for t in range(t_steps):
            q, k, v = qs[t], ks[t], vs[t]
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc = sc * scale
            if binarize_scores:
                a = (sc - delta_ref[0, 0] >= 0).astype(jnp.float32)
            else:
                a = sc
            if causal:
                rows = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
                cols = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
                a = jnp.where(rows >= cols, a, 0.0)
            ctx = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            o_ref[0, 0, t] = ctx.astype(dtype)
        cnt_ref[h * 4 + 3] += jnp.int32(2 * t_steps)


def fused_ssa(x: jax.Array, w3: jax.Array, scale3: Optional[jax.Array],
              aux: jax.Array, delta, *, family: str, num_heads: int,
              head_dim: int, scale: float, causal: bool = False,
              binarize_scores: bool = True, decay: float = 0.5,
              v_th: float = 1.0, soft_reset: bool = False,
              eps: float = 1e-5,
              interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Fused projection+attention SSA step (forward only — the engine
    wraps it in a custom VJP whose bwd recomputes ``reference_bundle``).

    Args:
      x: ``(T, B, L, K)`` — {0,1} spikes (vision family) or normed
        currents (token family), activation dtype.
      w3: ``(3, K, H*hd)`` stacked Q/K/V weights (quantized codes arrive
        pre-cast to the activation dtype, mirroring dense_quant_linear).
      scale3: ``(3, H*hd)`` fp32 per-channel quantization scales, or
        ``None`` for fp-native weights.
      aux: projection epilogue operand — family ``'bn'``: ``(3, 4,
        H*hd)`` fp32 rows ``[mean, var, scale, bias]`` per projection
        (eval-mode running stats + affine); family ``'rope'``: ``(2, L,
        hd//2)`` fp32 ``[cos; sin]`` tables (applied to Q/K only).
      delta: score binarization threshold (scalar).
      scale: python-float score scale (1/sqrt(hd) per Eq. 2).

    Returns:
      (context ``(T, B, L, H*hd)`` activation dtype,
       counts ``(H, 4)`` int32 — *executed* dot sub-steps per head for
       the Q/K/V projection phases and the attention phase).
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown fused-SSA family {family!r} "
                         f"(expected bn|rope)")
    t, b, l, k_dim = x.shape
    q_dim = num_heads * head_dim
    assert w3.shape == (3, k_dim, q_dim), w3.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = x.dtype
    xb = jnp.transpose(x, (1, 0, 2, 3))              # (B, T, L, K)
    delta_op = jnp.asarray(delta, jnp.float32).reshape(1, 1)

    # Per-head operands are laid out head-major so every block's two
    # minor dims are whole array dims: Mosaic's (8, 128) tiling rule then
    # holds for any head_dim (a (K, hd) lane slice of the (K, H*hd)
    # weights would not, at hd = 32 or 64).
    heads = lambda a: jnp.moveaxis(
        a.reshape(*a.shape[:-1], num_heads, head_dim), -2, 1)
    w_idx = lambda bi, hi, pi: (jnp.minimum(pi, 2), hi, 0, 0)
    in_specs = [
        pl.BlockSpec((1, t, l, k_dim), lambda bi, hi, pi: (bi, 0, 0, 0)),
        pl.BlockSpec((1, 1, k_dim, head_dim), w_idx),
    ]
    operands = [xb, heads(w3)]
    has_scale = scale3 is not None
    if not has_scale:
        # uniform kernel signature; multiplying fp32 by 1.0 is a bitwise
        # identity, so the fp-native path is unaffected
        scale3 = jnp.ones((3, q_dim), jnp.float32)
    in_specs.append(pl.BlockSpec((1, 1, 1, head_dim), w_idx))
    operands.append(heads(scale3.astype(jnp.float32)[:, None, :]))
    if family == "bn":
        assert aux.shape == (3, 4, q_dim), aux.shape
        in_specs.append(pl.BlockSpec((1, 1, 4, head_dim), w_idx))
        operands.append(heads(aux.astype(jnp.float32)))
    else:
        assert aux.shape == (2, l, head_dim // 2), aux.shape
        in_specs.append(pl.BlockSpec(
            (2, l, head_dim // 2), lambda bi, hi, pi: (0, 0, 0)))
        operands.append(aux.astype(jnp.float32))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(delta_op)

    kernel = functools.partial(
        _kernel, family=family, t_steps=t, l=l, k_dim=k_dim,
        head_dim=head_dim, scale=float(scale), causal=causal,
        binarize_scores=binarize_scores, decay=float(decay),
        v_th=float(v_th), soft_reset=soft_reset, eps=float(eps),
        has_scale=has_scale, dtype=dtype)

    out, cnt = pl.pallas_call(
        kernel,
        grid=(b, num_heads, 4),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, t, l, head_dim),
                         lambda bi, hi, pi: (bi, hi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, num_heads, t, l, head_dim), dtype),
            jax.ShapeDtypeStruct((num_heads * 4,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, l, head_dim), dtype),     # q spikes
            pltpu.VMEM((t, l, head_dim), dtype),     # k spikes
            pltpu.VMEM((t, l, head_dim), dtype),     # v spikes
        ],
        interpret=interpret,
    )(*operands)
    ctx = jnp.transpose(out, (2, 0, 3, 1, 4)).reshape(t, b, l, q_dim)
    return ctx, cnt.reshape(num_heads, 4)


def reference_bundle(x: jax.Array, w3: jax.Array,
                     scale3: Optional[jax.Array], aux: jax.Array, delta,
                     scfg: SpikingConfig, *, family: str, num_heads: int,
                     head_dim: int, scale: float, causal: bool = False,
                     eps: float = 1e-5) -> jax.Array:
    """The sequential oracle: term-for-term the ``overlap='off'`` layer
    composition (dense fp32-accumulated projections -> BN affine / RoPE
    -> ``lif_scan`` -> jnp binary attention), on the same raw operands
    the kernel sees. The fused custom VJP recomputes through this in
    bwd, so fused gradients are the sequential path's gradients by
    construction (surrogate LIF/binarize jvps included)."""
    t, b, l, _ = x.shape
    q_dim = num_heads * head_dim
    half = head_dim // 2
    projected = []
    for j in range(3):
        with annotate("sparse_engine.dense"):
            acc = jnp.dot(x, w3[j], preferred_element_type=jnp.float32)
            if scale3 is not None:
                acc = acc * scale3[j].astype(jnp.float32)
            y = acc.astype(x.dtype)
        if family == "bn":
            mean, var = aux[j, 0], aux[j, 1]
            y32 = y.astype(jnp.float32)
            y32 = (y32 - mean) * jax.lax.rsqrt(var + eps)
            y32 = y32 * aux[j, 2] + aux[j, 3]
            y = y32.astype(x.dtype)
        elif j < 2:                                  # rope on q, k
            y5 = y.reshape(t, b, l, num_heads, head_dim)
            cos = aux[0][None, None, :, None, :]
            sin = aux[1][None, None, :, None, :]
            x1 = y5[..., :half].astype(jnp.float32)
            x2 = y5[..., half:].astype(jnp.float32)
            y = jnp.concatenate([x1 * cos - x2 * sin,
                                 x2 * cos + x1 * sin],
                                -1).astype(x.dtype).reshape(t, b, l, q_dim)
        s_j, _ = lif_scan(y, scfg)
        projected.append(s_j)
    fold = lambda u: u.reshape(t * b, l, num_heads,
                               head_dim).transpose(0, 2, 1, 3)
    with annotate("binary_engine.jnp"):
        q, k, v = (fold(u) for u in projected)
        scores = jnp.einsum("...qd,...kd->...qk", q, k,
                            preferred_element_type=jnp.float32) * scale
        if scfg.binarize_scores:
            attn = binarize(scores, delta, scfg.surrogate_alpha)
        else:
            attn = scores
        if causal:
            mask = jnp.tril(jnp.ones((l, l), bool))
            attn = jnp.where(mask, attn, 0.0)
        ctx = jnp.einsum("...qk,...kd->...qd", attn, v,
                         preferred_element_type=jnp.float32).astype(q.dtype)
        return ctx.transpose(0, 2, 1, 3).reshape(t, b, l, q_dim)
