"""Fused whole-layer step — the layer-program the dual-engine overlay runs.

PR 6 (``kernels/fused_ssa.py``) fused the SSA *bundle* (Q/K/V
projections + binary attention) onto one Pallas grid; the MLP of layer
l still ran sequentially after attention, the fused projections only
skipped at spike-*slab* granularity, and the binary phases never
skipped at all. This kernel extends the fusion to the **entire encoder
layer** — the paper's orchestrator overlaps the sparse and binary
engines across the whole layer dataflow, not just the bundle:

Grid ``(B, P, H)`` (``overlap='fused'``) or ``(B, T, P, H)``
(``overlap='pipeline'`` — the timestep/layer axis from ROADMAP made a
grid axis: every phase advances one timestep at a time, LIF membranes
ride VMEM scratch across the T axis, and on a pipelined backend layer
l+1's projection phases stream in behind layer l's MLP tiles on the
same wavefront), with P = 8 per-head phases (:data:`LAYER_PHASES`):

  sparse engine: ``q / k / v`` projections (+ BN/RoPE epilogue + LIF),
                 ``wo`` head-slice, ``up`` / ``down`` MLP ff-chunks
  binary engine: ``qkt`` (scores + binarize + mask), ``qktv`` (context)

Three sparsity mechanisms, all *measured* (only executed sub-blocks
reach the counts output) and all exact (skipped work contributes +0):

* decoded chunks (``sparse='decoded'``): each spike slab's live
  entries are rank-decoded on-device into compacted chunks
  (:func:`repro.kernels.spike_decode.slab_decode`) and the projection
  phases contract chunk by chunk, skipping chunks past per-L-block
  pow2 occupancy-bucket caps — the fine-grained decoded datapath,
  reachable from inside the fused step. Restricted to the spike-driven
  family (vision): splitting the K contraction into decoded chunks is
  only order-free in fp32 — hence bitwise — when every partial sum is
  exact ({0,1} spikes x dyadic / integer-code weights, DESIGN.md §4);
  the token family's projections consume *analog* normed currents, so
  there ``decoded`` degenerates to the tile skip (same dispatch
  outcome, still bitwise). ``sparse='tile'`` keeps block-granular
  occupancy skips (the PR 6 slab skip, refined to L-block resolution).
* occupancy map for the binary phases: per (head, key/value-axis
  L-block) the ``qkt`` phase skips all-dark key blocks (their scores
  are exact zeros, which binarize to zero whenever delta > 0 — when
  delta <= 0 the predicate forces execution) and the ``qktv`` phase
  skips blocks whose binarized scores or value spikes are all dark —
  the byte-level-write analogy of the paper's binary engine
  (DESIGN.md §11).
* ``wo`` / ``up`` / ``down`` skip all-dark input row blocks
  (bias-free linears: a zero row block contributes exact fp32 zeros).

The counts output is a ``(H, 8, n_l_blocks)`` int32 occupancy map —
the PR 6 ``(H, 4)`` executed-step counts extended per phase and per
L-block — consumed by ``core.dual_engine.fused_step_metrics`` for the
per-phase measured hidden fraction.

Bit-exactness (DESIGN.md §4 contract): every contraction accumulates
fp32 over exact or un-split operands, epilogues repeat the reference
expressions (``nn.batchnorm`` eval affine, ``nn.rope``, ``nn.rmsnorm``,
``core.spiking.lif_step``) on identical dtypes, and the fused / pipeline
grids execute identical math — so :func:`reference_layer` below (the
sequential layer composition ``models/spikingformer._block`` /
``models/transformer.apply_layer`` used to inline) is matched bitwise
on the layer output, and is the recompute target of the fused path's
custom VJP (``core.engine``).

Mosaic layout (pinned by ``tests/test_tpu_compile.py``): per-head
weight, scale and epilogue operands are laid out head-major so each
block's two minor dims are whole array dims; the decoded caps and the
occupancy map live in SMEM; every phase walks L in ``l_block`` row
blocks through static ref slices; and the call asks for the VMEM its
resident (T, L, .) working set needs (:data:`VMEM_CAP`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scopes import annotate
from repro.core.spiking import SpikingConfig, lif_scan

FAMILIES = ("bn", "rope")
# per-head phases of the layer program: three sparse projections, the
# two binary-engine phases, then the post-attention sparse phases
LAYER_PHASES = ("q", "k", "v", "qkt", "qktv", "wo", "up", "down")
N_PHASES = len(LAYER_PHASES)


# v5e's VMEM is 128 MiB; Mosaic's default scoped limit is 16 MiB. The
# layer program keeps a batch row's whole (T, L, .) working set resident,
# so it asks for what its blocks and scratch need (reckoned by
# :func:`_vmem_bytes`) plus headroom for Mosaic's own temporaries, and
# refuses shapes whose need passes this cap.
VMEM_CAP = 100 << 20
VMEM_HEADROOM = 16 << 20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer: the two minor dims round up to the
    (sublane, 128-lane) tile of the dtype (8 rows of 32 bits, so 16 for
    bf16 and 32 for int8)."""
    item = jnp.dtype(dtype).itemsize
    *lead, r, c = (1,) * (2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // item)
    n = 1
    for d in lead:
        n *= d
    return n * (-(-r // sub) * sub) * (-(-c // 128) * 128) * item


def _kernel(*refs, family, decoded, pipeline, t_steps, l, d_model,
            head_dim, num_heads, ffc, l_block, c_block, nc, nlb, scale,
            causal, binarize_scores, decay, v_th, soft_reset, eps,
            norm_eps, dtype):
    (x_ref, s_ref, w3_ref, wo_ref, w1_ref, w2_ref, sc3_ref, sco_ref,
     sc1_ref, sc2_ref, auxp_ref, auxo_ref, aux1_ref, aux2_ref,
     delta_ref) = refs[:15]
    n_in = 17 if decoded else 15
    cid_ref, cap_ref = refs[15:17] if decoded else (None, None)
    (o_ref, cnt_ref, sq, sk, sv, scr, ctxs, hids, attn_acc, dn_acc, x1s,
     s2s, uq, uk, uv, us2, uh) = refs[n_in:]
    if pipeline:
        b, ti = pl.program_id(0), pl.program_id(1)
        p, h = pl.program_id(2), pl.program_id(3)
        trange = (ti,)
        first_step = (b == 0) & (ti == 0) & (p == 0) & (h == 0)
    else:
        b = pl.program_id(0)
        p, h = pl.program_id(1), pl.program_id(2)
        trange = tuple(range(t_steps))
        first_step = (b == 0) & (p == 0) & (h == 0)
    half = head_dim // 2
    # every phase walks the L axis in l_block row blocks: the skip
    # granularity of the occupancy map, and static ref slices that Mosaic
    # stores with masks where a block is not a multiple of 8 rows
    blocks = [(lb, lb * l_block, min(l, (lb + 1) * l_block))
              for lb in range(nlb)]
    slot = lambda t: h * t_steps + t          # flattened (head, t) scratch
    delta = delta_ref[0, 0]
    dot = lambda a, w: jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(first_step)
    def _init_counts():
        def zero(i, c):
            cnt_ref[i] = jnp.int32(0)
            return c
        jax.lax.fori_loop(0, num_heads * N_PHASES * nlb, zero, 0)

    def _bump(col, nexec):
        # occupancy map (SMEM): executed sub-blocks of phase `col`
        for lb in range(nlb):
            i = (h * N_PHASES + col) * nlb + lb
            cnt_ref[i] = cnt_ref[i] + nexec[lb]

    def _lif(u_ref, uslot, t, y, r0, r1):
        # one lif_step on rows r0:r1; the membrane rides scratch so the
        # pipeline grid carries it across the T axis (the fused grid
        # round-trips it within one invocation — identical values)
        if pipeline:
            u = jnp.where(t == 0, jnp.zeros_like(y), u_ref[uslot, r0:r1])
        else:
            u = jnp.zeros_like(y) if t == 0 else u_ref[uslot, r0:r1]
        u = decay * u + y
        s_t = (u - v_th >= 0).astype(dtype)
        u = u - s_t * v_th if soft_reset else u * (1.0 - s_t)
        u_ref[uslot, r0:r1] = u
        return s_t

    def _bn(y, rows):
        # nn.batchnorm eval affine; rows: (4, C) [mean, var, scale, bias]
        y32 = y.astype(jnp.float32)
        y32 = (y32 - rows[0:1]) * jax.lax.rsqrt(rows[1:2] + eps)
        return (y32 * rows[2:3] + rows[3:4]).astype(dtype)

    def _skip_dot(rows, w, n):
        # occupancy skip: an all-dark row block contributes exact zeros
        occ = jnp.any(rows != 0)
        acc = jax.lax.cond(occ, lambda: dot(rows, w),
                           lambda: jnp.zeros((rows.shape[0], n),
                                             jnp.float32))
        return acc, occ.astype(jnp.int32)

    def project(dst, u_ref, col, roped):
        # sparse-engine projection phase: per (timestep, L-block) either
        # the decoded datapath's compacted chunks under the bucket caps or
        # the tile path's occupancy-skipped dot, then the projection
        # epilogue (quant scale, BN affine / RoPE) and LIF — per head
        w = w3_ref[0, 0]                                 # (K, hd)
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            for lb, r0, r1 in blocks:
                rows = s_ref[0, t, r0:r1]                # (r, K)
                if decoded:
                    cids = cid_ref[0, t, r0:r1]
                    cap = cap_ref[(b * t_steps + t) * nlb + lb]
                    acc = jnp.zeros((r1 - r0, head_dim), jnp.float32)
                    for ci in range(nc):
                        live = ci * c_block < cap
                        acc = jax.lax.cond(
                            live,
                            lambda a=acc, ci=ci: a + dot(
                                jnp.where(cids == ci, rows, 0), w),
                            lambda a=acc: a)
                        nexec[lb] += live.astype(jnp.int32)
                else:
                    acc, n = _skip_dot(rows, w, head_dim)
                    nexec[lb] += n
                y = (acc * sc3_ref[0, 0].astype(jnp.float32)).astype(dtype)
                if family == "bn":
                    y = _bn(y, auxp_ref[0, 0])
                elif roped:                              # rope: q, k only
                    cos, sin = auxp_ref[0, r0:r1], auxp_ref[1, r0:r1]
                    x1 = y[:, :half].astype(jnp.float32)
                    x2 = y[:, half:].astype(jnp.float32)
                    y = jnp.concatenate([x1 * cos - x2 * sin,
                                         x2 * cos + x1 * sin],
                                        -1).astype(dtype)
                dst[slot(t), r0:r1] = _lif(u_ref, h, t, y, r0, r1)
        _bump(col, nexec)

    @pl.when(p == 0)
    def _q():
        project(sq, uq, 0, roped=True)

    @pl.when(p == 1)
    def _k():
        project(sk, uk, 1, roped=True)

    @pl.when(p == 2)
    def _v():
        project(sv, uv, 2, roped=False)

    def _score_block(q_t, k_blk, r0, n):
        sc = jax.lax.dot_general(q_t, k_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = sc * scale
        if binarize_scores:
            a = (sc - delta >= 0).astype(jnp.float32)
        else:
            a = sc
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (l, n), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (l, n), 1) + r0
            a = jnp.where(rows >= cols, a, 0.0)
        return a

    def _qkt_live(k_blk):
        # an all-dark key block scores to exact zeros, which binarize to
        # zero whenever delta > 0; when delta <= 0 (or scores stay
        # analog) the block must execute — the predicate says so, so the
        # skip stays exact (+0) by construction
        live = jnp.any(k_blk != 0)
        if binarize_scores:
            live = live | (delta <= 0)
        else:
            live = live | True
        return live

    @pl.when(p == 3)
    def _qkt():
        # binary engine, score phase: binarized+masked score blocks land
        # in VMEM scratch for the qktv phase; dark blocks skip and the
        # skip is recorded in the occupancy map
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            q_t = sq[slot(t)]
            for lb, r0, r1 in blocks:
                k_blk = sk[slot(t), r0:r1]
                live = _qkt_live(k_blk)
                scr[slot(t), :, r0:r1] = jax.lax.cond(
                    live,
                    lambda kb=k_blk, r=r0, n=r1 - r0:
                        _score_block(q_t, kb, r, n),
                    lambda n=r1 - r0: jnp.zeros((l, n), jnp.float32))
                nexec[lb] += live.astype(jnp.int32)
        _bump(3, nexec)

    @pl.when(p == 4)
    def _qktv():
        # binary engine, context phase: contract the stashed score
        # blocks with the value blocks; a block whose scores or value
        # spikes are all dark contributes exact +0 and is skipped
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            ctx = jnp.zeros((l, head_dim), jnp.float32)
            for lb, r0, r1 in blocks:
                v_blk = sv[slot(t), r0:r1]
                a_blk = scr[slot(t), :, r0:r1]
                live = _qkt_live(sk[slot(t), r0:r1]) & jnp.any(v_blk != 0)
                ctx = ctx + jax.lax.cond(
                    live,
                    lambda a=a_blk, v=v_blk: dot(a, v.astype(jnp.float32)),
                    lambda: jnp.zeros((l, head_dim), jnp.float32))
                nexec[lb] += live.astype(jnp.int32)
            ctxs[slot(t)] = ctx.astype(dtype)
        _bump(4, nexec)

    @pl.when(p == 5)
    def _wo():
        # sparse engine, output projection: head h's context slice times
        # wo's matching row block, fp32-accumulated across heads (exact:
        # binary-attention contexts are integer counts, weights dyadic);
        # dark context row blocks skip. The last head runs the epilogue:
        # quant scale, bn_o (vision) -> residual -> input neuron /
        # ln2 rmsnorm (token) into the MLP input scratch.
        w = wo_ref[0]                                    # (hd, D)
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            @pl.when(h == 0)
            def _zero():
                attn_acc[t] = jnp.zeros((l, d_model), jnp.float32)
            for lb, r0, r1 in blocks:
                contrib, n = _skip_dot(ctxs[slot(t), r0:r1], w, d_model)
                attn_acc[t, r0:r1] = attn_acc[t, r0:r1] + contrib
                nexec[lb] += n

            @pl.when(h == num_heads - 1)
            def _epilogue():
                y = (attn_acc[t] * sco_ref[...].astype(jnp.float32)
                     ).astype(dtype)
                if family == "bn":
                    y = _bn(y, auxo_ref[...])
                x1 = x_ref[0, t] + y                     # residual stream
                x1s[t] = x1
                if family == "bn":
                    s2s[t] = _lif(us2, 0, t, x1, 0, l)   # input neuron
                else:                                    # ln2 (nn.rmsnorm)
                    x32 = x1.astype(jnp.float32)
                    var = jnp.mean(jnp.square(x32), axis=-1,
                                   keepdims=True)
                    s2s[t] = (x32 * jax.lax.rsqrt(var + norm_eps)
                              * auxo_ref[...].astype(jnp.float32)
                              ).astype(dtype)
        _bump(5, nexec)

    @pl.when(p == 6)
    def _up():
        # sparse engine, MLP up: ff-chunk h of w1 against the full-D
        # spike (vision) / normed-current (token) rows; epilogue
        # bn_1 + LIF (vision) or LIF (token) into the hidden spikes
        w = w1_ref[0]                                    # (D, ffc)
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            for lb, r0, r1 in blocks:
                acc, n = _skip_dot(s2s[t, r0:r1], w, ffc)
                nexec[lb] += n
                y = (acc * sc1_ref[0].astype(jnp.float32)).astype(dtype)
                if family == "bn":
                    y = _bn(y, aux1_ref[0])
                hids[slot(t), r0:r1] = _lif(uh, h, t, y, r0, r1)
        _bump(6, nexec)

    @pl.when(p == 7)
    def _down():
        # sparse engine, MLP down: ff-chunk h of w2 against chunk h's
        # hidden spikes, fp32-accumulated across chunks; the last chunk
        # runs the epilogue (quant scale, bn_2, residual) and writes
        # the layer output
        w = w2_ref[0]                                    # (ffc, D)
        nexec = [jnp.int32(0)] * nlb
        for t in trange:
            @pl.when(h == 0)
            def _zero():
                dn_acc[t] = jnp.zeros((l, d_model), jnp.float32)
            for lb, r0, r1 in blocks:
                contrib, n = _skip_dot(hids[slot(t), r0:r1], w, d_model)
                dn_acc[t, r0:r1] = dn_acc[t, r0:r1] + contrib
                nexec[lb] += n

            @pl.when(h == num_heads - 1)
            def _epilogue():
                y = (dn_acc[t] * sc2_ref[...].astype(jnp.float32)
                     ).astype(dtype)
                if family == "bn":
                    y = _bn(y, aux2_ref[...])
                o_ref[0, t] = x1s[t] + y
        _bump(7, nexec)


def fused_layer(x: jax.Array, s: jax.Array, w3: jax.Array, wo: jax.Array,
                w1: jax.Array, w2: jax.Array,
                scales: Optional[Tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array]],
                auxp: jax.Array, auxo: jax.Array,
                aux1: Optional[jax.Array], aux2: Optional[jax.Array],
                delta, *, family: str, num_heads: int, head_dim: int,
                scale: float, causal: bool = False, sparse: str = "tile",
                pipeline: bool = False, binarize_scores: bool = True,
                decay: float = 0.5, v_th: float = 1.0,
                soft_reset: bool = False, eps: float = 1e-5,
                norm_eps: float = 1e-6, l_block: int = 128,
                c_block: int = 128, interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Fused whole-layer step (forward only — ``core.engine`` wraps it
    in a custom VJP whose bwd recomputes :func:`reference_layer`).

    Args:
      x: ``(T, B, L, D)`` layer input — membrane currents, the residual
        stream (activation dtype).
      s: ``(T, B, L, D)`` projection-phase input: ``LIF(x)`` spikes
        (vision family) or the ln1-normed currents (token family).
      w3: ``(3, D, H*hd)`` stacked Q/K/V weights; wo ``(H*hd, D)``;
        w1 ``(D, F)``; w2 ``(F, D)`` with F = d_ff padded to a multiple
        of ``num_heads`` (zero pad — exact: padded channels normalize
        to zero through identity BN rows and never spike). Quantized
        codes arrive pre-cast to the activation dtype.
      scales: ``(scale3 (3, H*hd), scale_o (D,), scale_1 (F,),
        scale_2 (D,))`` fp32 per-channel quantization scales, or
        ``None`` for fp-native weights (multiplying fp32 by 1.0 is a
        bitwise identity, so the uniform kernel signature is free).
      auxp: projection epilogue — family ``'bn'``: ``(3, 4, H*hd)``
        rows [mean, var, scale, bias]; family ``'rope'``: ``(2, L,
        hd//2)`` [cos; sin] tables.
      auxo / aux1 / aux2: family ``'bn'``: the bn_o ``(4, D)``, bn_1
        ``(4, F)``, bn_2 ``(4, D)`` eval rows; family ``'rope'``: auxo
        is the ln2 rmsnorm scale ``(1, D)`` and aux1/aux2 are ignored.
      sparse: ``'tile'`` (L-block occupancy skip) or ``'decoded'``
        (compacted-chunk projection contraction; spike-driven family
        only — see module docstring).
      pipeline: run the ``(B, T, P, H)`` per-timestep wavefront grid
        instead of ``(B, P, H)``; outputs and counts are identical.

    Returns:
      (layer output ``(T, B, L, D)`` activation dtype,
       counts ``(H, 8, ceil(L / l_block))`` int32 — *executed* compute
       sub-blocks per head, phase (:data:`LAYER_PHASES`), L-block).

    Raises ValueError when the layer's VMEM working set passes
    :data:`VMEM_CAP`.
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown fused-layer family {family!r} "
                         f"(expected bn|rope)")
    if sparse not in ("tile", "decoded"):
        raise ValueError(f"unknown fused-layer sparse path {sparse!r}")
    t, b, l, k_dim = x.shape
    d_model = k_dim
    q_dim = num_heads * head_dim
    assert w3.shape == (3, k_dim, q_dim), w3.shape
    assert wo.shape == (q_dim, d_model), wo.shape
    ff = w1.shape[1]
    assert ff % num_heads == 0, "pad d_ff to a multiple of num_heads"
    ffc = ff // num_heads
    assert w2.shape == (ff, d_model), w2.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = x.dtype
    l_block = max(1, min(l_block, l))
    nlb = -(-l // l_block)
    # the decoded chunks need exact operands for order-free fp32
    # accumulation; the token family's projection input is analog
    decoded = sparse == "decoded" and family == "bn"
    delta_op = jnp.asarray(delta, jnp.float32).reshape(1, 1)

    xb = jnp.transpose(x, (1, 0, 2, 3))              # (B, T, L, D)
    sb = jnp.transpose(s, (1, 0, 2, 3))

    if scales is None:
        scales = (jnp.ones((3, q_dim), jnp.float32),
                  jnp.ones((d_model,), jnp.float32),
                  jnp.ones((ff,), jnp.float32),
                  jnp.ones((d_model,), jnp.float32))
    sc3, sco, sc1, sc2 = (jnp.asarray(a, jnp.float32) for a in scales)

    if pipeline:
        grid = (b, t, N_PHASES, num_heads)
        ix = lambda f: (lambda bi, ti, pi, hi: f(bi, pi, hi))
    else:
        grid = (b, N_PHASES, num_heads)
        ix = lambda f: (lambda bi, pi, hi: f(bi, pi, hi))

    # Per-head operands are laid out head-major, so every block's two
    # minor dims are whole array dims: Mosaic's (8, 128) tiling rule then
    # holds for any head_dim and ff-chunk width.
    def heads(a, n):                  # (..., H*n) -> (H, ..., n)
        return jnp.moveaxis(a.reshape(*a.shape[:-1], num_heads, n), -2, 0)

    whole = lambda a: pl.BlockSpec(a.shape, ix(lambda bi, pi, hi:
                                               (0,) * a.ndim))
    per_head = lambda a: pl.BlockSpec(
        (1, *a.shape[1:]), ix(lambda bi, pi, hi: (hi,) + (0,) * (a.ndim - 1)))
    per_proj = lambda a: pl.BlockSpec(
        (1, 1, *a.shape[2:]),
        ix(lambda bi, pi, hi: (jnp.minimum(pi, 2), hi, 0, 0)))
    per_row = pl.BlockSpec((1, t, l, d_model),
                           ix(lambda bi, pi, hi: (bi, 0, 0, 0)))

    w3h = jnp.swapaxes(heads(w3, head_dim), 0, 1)    # (3, H, K, hd)
    sc3h = jnp.swapaxes(heads(sc3[:, None, :], head_dim), 0, 1)
    woh = wo.reshape(num_heads, head_dim, d_model)   # (H, hd, D)
    w1h = heads(w1, ffc)                             # (H, D, ffc)
    w2h = w2.reshape(num_heads, ffc, d_model)        # (H, ffc, D)
    sc1h = heads(sc1[None, :], ffc)                  # (H, 1, ffc)
    operands = [xb, sb, w3h, woh, w1h, w2h, sc3h, sco.reshape(1, d_model),
                sc1h, sc2.reshape(1, d_model)]
    in_specs = [per_row, per_row, per_proj(w3h), per_head(woh),
                per_head(w1h), per_head(w2h), per_proj(sc3h),
                whole(operands[7]), per_head(sc1h), whole(operands[9])]
    if family == "bn":
        assert auxp.shape == (3, 4, q_dim), auxp.shape
        assert auxo.shape == (4, d_model), auxo.shape
        assert aux1.shape == (4, ff), aux1.shape
        assert aux2.shape == (4, d_model), aux2.shape
        auxph = jnp.swapaxes(heads(auxp.astype(jnp.float32), head_dim),
                             0, 1)                   # (3, H, 4, hd)
        aux1 = heads(aux1.astype(jnp.float32), ffc)  # (H, 4, ffc)
        aux_ops = [auxph, auxo, aux1, aux2]
        aux_specs = [per_proj(auxph), whole(auxo), per_head(aux1),
                     whole(aux2)]
    else:
        assert auxp.shape == (2, l, head_dim // 2), auxp.shape
        assert auxo.shape == (1, d_model), auxo.shape
        dummy = jnp.zeros((1, 1), jnp.float32)
        aux_ops = [auxp, auxo, dummy, dummy]
        aux_specs = [whole(a) for a in aux_ops]
    operands += [a.astype(jnp.float32) for a in aux_ops]
    in_specs += aux_specs
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands.append(delta_op)
    in_specs.append(smem)

    nc = 1
    c_blk = c_block
    if decoded:
        from repro.kernels.spike_decode import slab_decode
        cid, caps, c_blk, nc = slab_decode(s, l_block=l_block,
                                           c_block=c_block)
        operands += [cid, caps]
        in_specs += [per_row, smem]

    kernel = functools.partial(
        _kernel, family=family, decoded=decoded, pipeline=pipeline,
        t_steps=t, l=l, d_model=d_model, head_dim=head_dim,
        num_heads=num_heads, ffc=ffc, l_block=l_block, c_block=c_blk,
        nc=nc, nlb=nlb, scale=float(scale), causal=causal,
        binarize_scores=binarize_scores, decay=float(decay),
        v_th=float(v_th), soft_reset=soft_reset, eps=float(eps),
        norm_eps=float(norm_eps), dtype=dtype)

    f32 = jnp.float32
    scratch = [
        ((num_heads * t, l, head_dim), dtype),       # q spikes
        ((num_heads * t, l, head_dim), dtype),       # k spikes
        ((num_heads * t, l, head_dim), dtype),       # v spikes
        ((num_heads * t, l, l), f32),                # scores
        ((num_heads * t, l, head_dim), dtype),       # contexts
        ((num_heads * t, l, ffc), dtype),            # mlp hidden
        ((t, l, d_model), f32),                      # wo accum
        ((t, l, d_model), f32),                      # down accum
        ((t, l, d_model), dtype),                    # x + attn
        ((t, l, d_model), dtype),                    # mlp input
        ((num_heads, l, head_dim), dtype),           # q membrane
        ((num_heads, l, head_dim), dtype),           # k membrane
        ((num_heads, l, head_dim), dtype),           # v membrane
        ((1, l, d_model), dtype),                    # s2 membrane
        ((num_heads, l, ffc), dtype),                # mlp membrane
    ]
    # VMEM reckoning: scratch once, every VMEM block double-buffered by
    # the grid pipeline (the output row block included)
    blocks = [(spec.block_shape, op.dtype)
              for spec, op in zip(in_specs, operands)
              if spec.block_shape is not None]
    blocks.append(((1, t, l, d_model), dtype))
    need = sum(_vmem_bytes(sh, dt) for sh, dt in scratch) + \
        2 * sum(_vmem_bytes(sh, dt) for sh, dt in blocks)
    if need > VMEM_CAP:
        raise ValueError(
            f"fused layer needs {need / 2**20:.1f} MiB of VMEM at "
            f"(T, L, D, H, hd, ffc) = {(t, l, d_model, num_heads, head_dim, ffc)}"
            f"; the cap is {VMEM_CAP / 2**20:.0f} MiB")

    out, cnt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[per_row, smem],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, l, d_model), dtype),
            jax.ShapeDtypeStruct((num_heads * N_PHASES * nlb,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM(sh, dt) for sh, dt in scratch],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(VMEM_CAP, need + VMEM_HEADROOM)),
        interpret=interpret,
    )(*operands)
    return (jnp.transpose(out, (1, 0, 2, 3)),
            cnt.reshape(num_heads, N_PHASES, nlb))


def reference_layer(x: jax.Array, s: jax.Array, w3, wo, w1, w2,
                    scales, auxp, auxo, aux1, aux2, delta,
                    scfg: SpikingConfig, *, family: str, num_heads: int,
                    head_dim: int, scale: float, causal: bool = False,
                    eps: float = 1e-5, norm_eps: float = 1e-6
                    ) -> jax.Array:
    """The sequential oracle: term-for-term the ``overlap='off'`` layer
    composition (the SSA bundle via ``fused_ssa.reference_bundle``, then
    wo + epilogue + residual, input neuron / ln2, and the spiking MLP)
    on the same raw operands the kernel sees. The fused custom VJP
    recomputes through this in bwd, so fused-layer gradients are the
    sequential path's gradients by construction (surrogate LIF /
    binarize jvps included)."""
    from repro.kernels.fused_ssa import reference_bundle
    if scales is None:
        sc3 = sco = sc1 = sc2 = None
    else:
        sc3, sco, sc1, sc2 = scales

    def lin(u, w, sc):
        with annotate("sparse_engine.dense"):
            acc = jnp.dot(u, w, preferred_element_type=jnp.float32)
            if sc is not None:
                acc = acc * sc.astype(jnp.float32)
            return acc.astype(u.dtype)

    def bn(u, aux):
        u32 = u.astype(jnp.float32)
        u32 = (u32 - aux[0]) * jax.lax.rsqrt(aux[1] + eps)
        return (u32 * aux[2] + aux[3]).astype(x.dtype)

    ctx = reference_bundle(s, w3, sc3, auxp, delta, scfg, family=family,
                           num_heads=num_heads, head_dim=head_dim,
                           scale=scale, causal=causal, eps=eps)
    y = lin(ctx, wo, sco)
    if family == "bn":
        y = bn(y, auxo)
    x1 = x + y
    if family == "bn":
        s2, _ = lif_scan(x1, scfg)
    else:
        x32 = x1.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        s2 = (x32 * jax.lax.rsqrt(var + norm_eps)
              * auxo[0].astype(jnp.float32)).astype(x.dtype)
    up = lin(s2, w1, sc1)
    if family == "bn":
        up = bn(up, aux1)
    hid, _ = lif_scan(up, scfg)
    dn = lin(hid, w2, sc2)
    if family == "bn":
        dn = bn(dn, aux2)
    return x1 + dn
