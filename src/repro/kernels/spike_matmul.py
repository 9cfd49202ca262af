"""Block-sparse spike matmul — the sparse engine's MXU adaptation.

FireFly-T's sparse engine skips zero spikes at bit granularity with
multi-lane decoders + out-of-order workers. The MXU's profitable skip
granularity is a whole VMEM tile (DESIGN.md §3): this kernel computes
``y = s @ w`` (spikes x weights) with a per-(block_m x block_k) *occupancy
bitmap* computed upfront (the block-granular analogue of the decoder's
bitmap), and skips the inner dot entirely for all-zero spike blocks via
``@pl.when`` — no weight fetch, no MACs, matching Observation 1 (sparsity
is uniform across the spatial-temporal grid, so whole-tile skips fire
often at >=75% sparsity only when channel-blocks are coherently sparse;
the occupancy reduction itself is the multi-lane decode).

Grid: (nM, nN, nK), K innermost; fp32 accumulator in the revisited output
block. The occupancy map is a tiny flattened (nM * nK,) int32 table handed
to the kernel as a scalar-prefetch operand: it lives in SMEM for the whole
grid, where the ``@pl.when`` predicate reads it as a scalar (a (1, 1)
VMEM block per step would break Mosaic's (8, 128) tiling rule).
A fused bias lands on the last K step, after the final accumulation, so
the dense reference (fp32 dot, then bias) is reproduced term-for-term.

Shapes that don't divide the block sizes are zero-padded: padded K
columns contribute exact fp32 zeros (and all-zero padded blocks are
skipped by occupancy anyway), padded M rows / N columns are sliced off.
``spike_matmul_batched`` folds arbitrary leading ``(T, B, ...)`` dims
into M — the layout every model activation ``(T, B, L, D)`` arrives in.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import pad_to_multiple


def _live(occ_ref):
    """This step's spike block has a non-zero (scalar SMEM read)."""
    nk = pl.num_programs(2)
    return occ_ref[pl.program_id(0) * nk + pl.program_id(2)] > 0


def _kernel(occ_ref, s_ref, w_ref, o_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(_live(occ_ref))
    def _compute():
        s = s_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        o_ref[...] += jax.lax.dot_general(
            s, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _kernel_bias(occ_ref, s_ref, w_ref, b_ref, o_ref, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(_live(occ_ref))
    def _compute():
        s = s_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        o_ref[...] += jax.lax.dot_general(
            s, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _bias():
        o_ref[...] += b_ref[...].astype(jnp.float32)


def _qkernel(occ_ref, s_ref, w_ref, scale_ref, o_ref, acc_ref, *, nk):
    """Quantized-weight body: spike {0,1} rows x int8 weight rows with an
    **int32 accumulator** in VMEM scratch (the MXU's native int8 x int8 ->
    int32 form, the TPU analogue of FireFly-T's int8 DSP datapath),
    per-output-channel fp32 scale applied in the epilogue on the last K
    step. The occupancy skip is unchanged: a dark spike block fetches no
    weights and adds no MACs, whatever the weight dtype."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(occ_ref))
    def _compute():
        acc_ref[...] += int8_dot(s_ref[...], w_ref[...])

    @pl.when(ki == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * \
            scale_ref[...].astype(jnp.float32)


def _qkernel_bias(occ_ref, s_ref, w_ref, scale_ref, b_ref, o_ref, acc_ref,
                  *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(occ_ref))
    def _compute():
        acc_ref[...] += int8_dot(s_ref[...], w_ref[...])

    @pl.when(ki == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * \
            scale_ref[...].astype(jnp.float32) + \
            b_ref[...].astype(jnp.float32)


def int8_dot(s: jax.Array, w: jax.Array) -> jax.Array:
    """int32-exact ``s @ w`` on the MXU's int8 path: s int8 spikes, or
    int32 binary-attention counts in [0, 2**14), against int8 weight
    codes. The MXU multiplies int8 x int8 only (Mosaic lowers no int32
    operand), so counts split into two 7-bit digits: ``lo + 128 * hi``,
    each digit an exact int8 — the two int32-accumulated dots sum to the
    exact product."""
    dot = lambda a: jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    if s.dtype == jnp.int8:
        return dot(s)
    return dot((s & 127).astype(jnp.int8)) + \
        dot((s >> 7).astype(jnp.int8)) * 128


def block_occupancy(s: jax.Array, block_m: int, block_k: int) -> jax.Array:
    """(M, K) spikes -> (nM, nK) int32 any-nonzero per block."""
    m, k = s.shape
    occ = (s != 0).reshape(m // block_m, block_m, k // block_k,
                           block_k).any(axis=(1, 3))
    return occ.astype(jnp.int32)


def spike_matmul(s: jax.Array, w: jax.Array, *,
                 bias: Optional[jax.Array] = None,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 occupancy: Optional[jax.Array] = None,
                 out_dtype=None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """y = s @ w (+ bias); s: (M, K) {0,1} spikes, w: (K, N) weights ->
    (M, N) fp32 cast to ``out_dtype`` (default w.dtype; pass jnp.float32
    to keep the raw accumulator — the engine does, so mixed weight/
    activation dtypes round once, not twice). Zero spike blocks are
    skipped; shapes that don't divide the blocks are zero-padded and
    sliced back."""
    m, k = s.shape
    k2, n = w.shape
    assert k == k2
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    sp = pad_to_multiple(pad_to_multiple(s, 0, block_m), 1, block_k)
    wp = pad_to_multiple(pad_to_multiple(w, 0, block_k), 1, block_n)
    mp, kp = sp.shape
    np_ = wp.shape[1]
    occ = block_occupancy(sp, block_m, block_k) if occupancy is None \
        else occupancy

    grid = (mp // block_m, np_ // block_n, kp // block_k)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, occ: (mi, ki)),
        pl.BlockSpec((block_k, block_n), lambda mi, ni, ki, occ: (ki, ni)),
    ]
    operands = [sp, wp]
    if bias is None:
        kernel = _kernel
    else:
        kernel = functools.partial(_kernel_bias, nk=grid[2])
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda mi, ni, ki, occ: (0, ni)))
        operands.append(pad_to_multiple(bias.reshape(1, n), 1, block_n))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda mi, ni, ki, occ: (mi, ni))),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(occ.reshape(-1), *operands)
    return out[:m, :n].astype(w.dtype if out_dtype is None else out_dtype)


def quant_spike_matmul(s: jax.Array, qw: jax.Array, scale: jax.Array, *,
                       bias: Optional[jax.Array] = None,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 128,
                       occupancy: Optional[jax.Array] = None,
                       counts: bool = False,
                       interpret: Optional[bool] = None) -> jax.Array:
    """y = (s @ qw) * scale (+ bias); s: (M, K) {0,1} spikes, qw: (K, N)
    int8 weight codes, scale: (N,) fp32 per-output-channel -> (M, N) fp32.

    The integer half of the dual-side compression: spikes enter the MXU as
    int8 {0,1}, weights as int8 codes, partial sums accumulate in int32
    VMEM scratch (exact — no fp rounding inside the reduction), and the
    per-channel scale lands once in the epilogue. Under dyadic scales the
    result is bitwise equal to the fp32 reference on dequantized weights
    (DESIGN.md §8). Occupancy skip, padding, and tiling mirror
    :func:`spike_matmul`.

    ``counts=True`` declares the left operand as binary-attention integer
    counts (values up to L, not {0,1}): it rides int32 lanes instead of
    int8 — an int8 cast would silently wrap counts >= 128. The weight
    side (the bandwidth that quantization buys back) stays int8 either
    way.
    """
    m, k = s.shape
    k2, n = qw.shape
    assert k == k2, f"spikes K={k} vs weight K={k2}"
    assert qw.dtype == jnp.int8, f"quant kernel wants int8 codes, got " \
        f"{qw.dtype} (unpack int4 nibbles first)"
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    sp = pad_to_multiple(pad_to_multiple(s, 0, block_m), 1, block_k)
    wp = pad_to_multiple(pad_to_multiple(qw, 0, block_k), 1, block_n)
    mp, kp = sp.shape
    np_ = wp.shape[1]
    occ = block_occupancy(sp, block_m, block_k) if occupancy is None \
        else occupancy
    s_int = sp.astype(jnp.int32 if counts else jnp.int8)

    grid = (mp // block_m, np_ // block_n, kp // block_k)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, occ: (mi, ki)),
        pl.BlockSpec((block_k, block_n), lambda mi, ni, ki, occ: (ki, ni)),
        pl.BlockSpec((1, block_n), lambda mi, ni, ki, occ: (0, ni)),
    ]
    operands = [s_int, wp,
                pad_to_multiple(scale.reshape(1, n).astype(jnp.float32),
                                1, block_n)]
    if bias is None:
        kernel = functools.partial(_qkernel, nk=grid[2])
    else:
        kernel = functools.partial(_qkernel_bias, nk=grid[2])
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda mi, ni, ki, occ: (0, ni)))
        operands.append(pad_to_multiple(
            bias.reshape(1, n).astype(jnp.float32), 1, block_n))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda mi, ni, ki, occ: (mi, ni)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(occ.reshape(-1), *operands)
    return out[:m, :n]


def spike_matmul_batched(s: jax.Array, w: jax.Array, *,
                         bias: Optional[jax.Array] = None,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128,
                         interpret: Optional[bool] = None) -> jax.Array:
    """y = s @ w (+ bias) over arbitrary leading dims.

    s: (T, B, ..., K) spikes; the leading dims fold into the kernel's M —
    the spatial-temporal grid is one flat stream of rows to the sparse
    engine, so whole-tile skips fire across time steps and batch entries
    alike. Returns (T, B, ..., N) in w.dtype.
    """
    lead = s.shape[:-1]
    y = spike_matmul(s.reshape(-1, s.shape[-1]), w, bias=bias,
                     block_m=block_m, block_n=block_n, block_k=block_k,
                     interpret=interpret)
    return y.reshape(*lead, w.shape[1])
