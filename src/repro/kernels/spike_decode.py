"""Fine-grained sparse-decoder datapath: gather-compacted spike matmul.

The third sparse-engine mode (DESIGN.md §9). The tile kernel
(``spike_matmul``) only skips *whole* (block_m x block_k) spike tiles, so
fine-grained or ragged sparsity — rows whose live channels are scattered
rather than coherently blocked, the regime FireFly-S shows dominates real
SNN activations — gets zero speedup there. This module is the
MXU-granularity translation of the paper's full sparse-decoder pipeline
(§IV-A): decode, dispatch only the touched weight rows, and balance the
load so no worker waits on the densest row.

  paper (FPGA)                      | here (TPU)
  ----------------------------------|----------------------------------
  M-lane carry-lookahead decode     | ``decode_indices``: cumsum
  (Eq. 5 propagate/generate chain   | prefix-compaction — the rank of
  extracts M nonzero indices/cycle) | each set bit IS the lane/cycle it
                                    | decodes in; pinned equivalent to
                                    | ``core.sparsity.
                                    | multilane_decode_full`` by test
  out-of-order weight dispatch      | only the compacted chunks a row
  (fetch only touched weight rows)  | group's bucket reaches are
                                    | contracted; each chunk is the
                                    | row's live entries whose rank
                                    | falls in it
  input tracker / load balancing    | ``build_schedule``: rows sorted by
  (no worker stalls on a dense      | occupancy into block_m groups,
  word)                             | each group's capacity rounded to a
                                    | pow2 bucket — every grid step in a
                                    | bucket does uniform work, steps
                                    | past a group's bucket are skipped

The contraction: chunk ``c`` of row ``m`` holds the live entries whose
decode rank lies in ``[c * c_block, (c + 1) * c_block)``, and ``y[m] =
sum_c (s[m] masked to chunk c) @ w`` — fp32 (or int32) accumulation chunk
by chunk in compacted ascending-k order, bias after the final
accumulation. Every chunk sums a subset of the dense reference's terms
(plus exact zeros), so decoded-vs-dense is bitwise equal whenever fp32
accumulation is order-exact (dyadic weights; same contract as tile mode,
pinned in tests/test_spike_decode.py). The mask keeps the input *values*,
so the same kernel is exact for the binary-attention integer counts the
wo projection consumes.

The chunk is a mask over the resident (block_m, K) spike rows and not a
``w[idx]`` row gather: Mosaic lowers no gather across vregs, so on the
MXU each executed chunk is one (block_m, K) x (K, block_n) dot. A row
group with any live entry therefore costs at least one dense sweep, and
only whole groups of all-dark rows (which the occupancy sort gathers)
cost nothing: the decoded path does no less MXU work than the tile path
except where dark rows are scattered among live ones
(:func:`decoded_dot_fraction`, DESIGN.md §9).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import pad_to_multiple
from repro.kernels.spike_matmul import block_occupancy, int8_dot

# Crossover factor for ``sparse='auto'`` (DESIGN.md §9): on top of its
# dots the decoded path sorts the rows, stages the chunk ids (an (M, K)
# int32 operand beside the spikes) and masks each resident chunk, so its
# modeled MXU work must sit below the tile path's by at least this
# factor before auto picks it.
DECODED_OVERHEAD = 2.0


def pow2ceil(x: jax.Array) -> jax.Array:
    """Elementwise smallest power of two >= x (0 -> 0, 1 -> 1). Integer
    bit-twiddling via ``lax.clz`` — no float log2 round-off."""
    x = x.astype(jnp.int32)
    p = 1 << (32 - jax.lax.clz(jnp.maximum(x, 1) - 1))
    return jnp.where(x <= 1, jnp.maximum(x, 0), p)


def decode_ranks(s: jax.Array, cap: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The M-lane decoder's output for each row of s: (rank (M, K) int32,
    occ (M,) int32). ``rank[m, k]`` is the position of entry k among row
    m's non-zeros (``cumsum(bits) - 1``; meaningless where s is zero) —
    exactly the slot the M-lane carry-lookahead decoder fires it in (lane
    ``rank % M`` of cycle ``rank // M``).

    ``cap`` (default K) statically bounds the compacted width; rows with
    more non-zeros than ``cap`` would be silently truncated downstream, so
    concrete inputs are guarded (traced inputs trust the caller's bound).
    """
    m, k = s.shape
    bits = s != 0
    occ = bits.sum(-1).astype(jnp.int32)
    cap = k if cap is None else min(cap, k)
    if cap < k and not isinstance(occ, jax.core.Tracer):
        hi = int(jnp.max(occ)) if m else 0
        if hi > cap:
            raise ValueError(f"decode cap {cap} < max row occupancy {hi}")
    return jnp.cumsum(bits, axis=-1).astype(jnp.int32) - 1, occ


def decode_indices(s: jax.Array, cap: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Compact each row's non-zero K-indices by cumsum prefix-compaction.

    s: (M, K). Returns (idx (M, cap) int32, occ (M,) int32): ``idx[m,
    :occ[m]]`` are the positions of row m's non-zeros, ascending; padding
    slots hold 0. Chunking ``idx`` by the lane count reproduces
    ``multilane_decode_full``'s per-cycle index sets — pinned by property
    test. ``cap`` as in :func:`decode_ranks`.
    """
    m, k = s.shape
    rank, occ = decode_ranks(s, cap)
    cap = k if cap is None else min(cap, k)
    slot = jnp.where(s != 0, rank, cap)          # dead bits -> spill slot
    cols = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None], (m, k))
    idx = jnp.zeros((m, cap + 1), jnp.int32).at[
        jnp.arange(m)[:, None], slot].set(cols, mode="drop")
    return idx[:, :cap], occ


def chunk_ids(s: jax.Array, c_block: int, cap: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """(cid (M, K) int32, occ (M,)): the compacted chunk each live entry
    decodes into (``rank // c_block``), -1 where s is zero. The kernels
    mask their resident spike rows with ``cid == chunk``."""
    rank, occ = decode_ranks(s, cap)
    return jnp.where(s != 0, rank // c_block, -1), occ


def build_schedule(occ: jax.Array, block_m: int, c_block: int, cap: int):
    """Occupancy-binned load-balancing schedule (the OoO/weight-dispatch
    analog). Rows sort ascending by occupancy into ``block_m`` groups;
    each group's capacity is its max occupancy rounded up to a pow2
    bucket (clipped to the padded compacted width). Uniform work per
    bucket: a grid step is either fully live or skipped, so no tile
    waits on the densest row — the dense rows share a group.

    occ: (M,) per-row non-zero counts (M % block_m == 0 — pad first).
    Returns dict with ``order`` (ascending-occupancy row permutation),
    ``caps`` (n_groups,), per-group ``steps``, ``executed``/``total``
    c_block-step counts per N tile, and ``mac_fraction`` =
    executed/total (the decoded path's modeled MAC share vs a dense
    sweep of the compacted width). Mirrored bit-for-bit by the numpy
    twin ``sim.balance_sim.bucket_schedule`` (cross-validated in tests
    and benchmarks/dual_engine_bench.py).
    """
    m = occ.shape[0]
    assert m % block_m == 0, f"pad rows first: {m} % {block_m}"
    cp = max(c_block, -(-cap // c_block) * c_block)
    order = jnp.argsort(occ)                      # stable, ascending
    gmax = occ[order].reshape(m // block_m, block_m).max(axis=1)
    caps = jnp.minimum(pow2ceil(gmax), cp).astype(jnp.int32)
    steps = -(-caps // c_block)
    nc = cp // c_block
    executed = steps.sum()
    total = (m // block_m) * nc
    return {"order": order, "caps": caps, "steps": steps,
            "executed": executed, "total": total, "padded_cap": cp,
            "mac_fraction": executed / total}


def decoded_dot_fraction(sched) -> float:
    """The decoded kernel's modeled MXU work as a share of one dense
    sweep, from a :func:`build_schedule` result. Each executed chunk is a
    full-K (block_m, K) x (K, block_n) dot, so a row group costs its
    ``steps`` dense sweeps: the share is executed chunks per row group.
    It is 1 or more whenever every group holds a live row, and falls
    below 1 only as far as the sort fills whole groups with dark rows."""
    return float(sched["executed"]) / sched["caps"].shape[0]


def choose_sparse_path(s: jax.Array, block_m: int, block_k: int) -> str:
    """Per-call tile-vs-decoded decision from the concrete occupancy
    histogram (``sparse='auto'``, DESIGN.md §9). The tile path costs its
    live-tile share; the decoded path costs :func:`decoded_dot_fraction`
    handicapped by ``DECODED_OVERHEAD``. Decoded wins only where the
    occupancy sort gathers dark rows, scattered among live ones, into
    whole dark groups that the tile map cannot skip.

    The occupancy reduction here is recomputed by the kernel's staging
    when 'decoded' wins — deliberate: the engine's custom-VJP static
    args can't carry arrays, the chooser only runs on eager (non-jit)
    calls, and the duplicated work is O(M*K), ~1/N of the matmul it
    gates.
    """
    m, k = s.shape
    bm, bk = min(block_m, m), min(block_k, k)
    sp = pad_to_multiple(pad_to_multiple(s, 0, bm), 1, bk)
    tile_frac = float(block_occupancy(sp, bm, bk).mean())
    smp = pad_to_multiple(s, 0, bm)
    occ = (smp != 0).sum(-1).astype(jnp.int32)
    dec_frac = decoded_dot_fraction(build_schedule(occ, bm, bk, cap=k))
    return "decoded" if dec_frac * DECODED_OVERHEAD < tile_frac else "tile"


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------


def _chunk(cid_ref, s_ref, ci):
    """Compacted chunk ``ci`` of the resident spike rows: the live entries
    whose decode rank falls in it, every other entry an exact zero."""
    return jnp.where(cid_ref[...] == ci, s_ref[...], 0)


def _live(caps_ref, c_block):
    """The chunk lies below this row group's bucket capacity (SMEM)."""
    return pl.program_id(2) * c_block < caps_ref[pl.program_id(0)]


def _kernel(caps_ref, s_ref, cid_ref, w_ref, *rest, c_block, nc, bias):
    b_ref, o_ref = rest if bias else (None, rest[0])
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(_live(caps_ref, c_block))
    def _compute():
        o_ref[...] += jax.lax.dot_general(
            _chunk(cid_ref, s_ref, ci).astype(jnp.float32),
            w_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if bias:
        @pl.when(ci == nc - 1)
        def _bias():                  # after the final accumulation,
            o_ref[...] += b_ref[...].astype(jnp.float32)  # like the dense ref


def _qkernel(caps_ref, s_ref, cid_ref, w_ref, scale_ref, *rest, c_block,
             nc, bias):
    """Quantized decoded body: int8 weight codes x the chunk's spike /
    count lanes with an int32 VMEM accumulator; per-output-channel fp32
    scale in the epilogue on the last grid step (which always executes —
    only the compute steps past a group's bucket are skipped)."""
    b_ref, o_ref, acc_ref = rest if bias else (None, *rest)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(caps_ref, c_block))
    def _compute():
        acc_ref[...] += int8_dot(_chunk(cid_ref, s_ref, ci), w_ref[...])

    @pl.when(ci == nc - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * \
            scale_ref[...].astype(jnp.float32)
        if bias:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = y


# ---------------------------------------------------------------------------
# staging shared by the fp32 and quantized entries
# ---------------------------------------------------------------------------


def _call(kernel, s, w, extra, *, block_m, block_n, c_block, cap,
          scratch, interpret):
    """Pad rows, decode them into chunk ids, sort by occupancy, build the
    bucket schedule, and run ``kernel`` over (row group, N tile, chunk).
    ``extra``: (1, N) per-channel operands after the weights. Returns the
    (M, N) fp32 result in the caller's row order."""
    m, k = s.shape
    n = w.shape[1]
    sp = pad_to_multiple(s, 0, block_m)
    cid, occ = chunk_ids(sp, c_block, cap)
    sched = build_schedule(occ, block_m, c_block,
                           cap=k if cap is None else min(cap, k))
    order = sched["order"]
    wp = pad_to_multiple(w, 1, block_n)
    mp, np_ = sp.shape[0], wp.shape[1]
    nc = sched["padded_cap"] // c_block
    grid = (mp // block_m, np_ // block_n, nc)
    row = lambda gi, ni, ci, caps: (gi, 0)
    chan = lambda gi, ni, ci, caps: (0, ni)
    in_specs = [pl.BlockSpec((block_m, k), row),
                pl.BlockSpec((block_m, k), row),
                pl.BlockSpec((k, block_n), chan)]
    in_specs += [pl.BlockSpec((1, block_n), chan) for _ in extra]
    out = pl.pallas_call(
        functools.partial(kernel, c_block=c_block, nc=nc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda gi, ni, ci, caps: (gi, ni)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(sched["caps"], sp[order], cid[order], wp,
      *[pad_to_multiple(a.reshape(1, n).astype(jnp.float32), 1, block_n)
        for a in extra])
    return out[jnp.argsort(order)][:m, :n]


def gather_spike_matmul(s: jax.Array, w: jax.Array, *,
                        bias: Optional[jax.Array] = None,
                        block_m: int = 128, block_n: int = 128,
                        c_block: int = 128, cap: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """y = s @ w (+ bias) through the gather-compacted decoded datapath.

    s: (M, K) spikes (or sparse integer counts — values are carried, not
    assumed binary), w: (K, N) -> (M, N) fp32. Each row's non-zeros are
    rank-decoded on-device, rows are binned into pow2 occupancy buckets
    (sorted into block_m groups), and grid steps past a group's bucket
    capacity are skipped — so executed chunks scale with the *occupancy
    histogram*, not with the live-tile count.

    ``cap`` statically bounds the compacted width (default K: exact for
    any input, still skipping by bucket); eager callers that know the max
    occupancy can pass a smaller cap to shrink the grid.
    """
    m, k = s.shape
    k2, n = w.shape
    assert k == k2, f"spikes K={k} vs weight K={k2}"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c_block = min(c_block, k if cap is None else max(1, cap))
    extra = [] if bias is None else [bias]
    return _call(functools.partial(_kernel, bias=bias is not None), s, w,
                 extra, block_m=min(block_m, m), block_n=min(block_n, n),
                 c_block=c_block, cap=cap, scratch=[], interpret=interpret)


def slab_decode(s: jax.Array, *, l_block: int, c_block: int
                ) -> Tuple[jax.Array, jax.Array, int, int]:
    """Stage the decoded datapath for the fused layer kernel
    (``kernels/fused_layer``): per-(timestep, batch) slab row decode
    plus per-L-block pow2 occupancy-bucket caps.

    Unlike :func:`_call`, rows are **not** permuted — the fused kernel
    consumes Q/K/V spikes in sequence order (the attention phases need
    them in place), so the bucket grouping is positional: each L-block
    of ``l_block`` consecutive rows gets capacity ``min(pow2ceil(max
    occupancy in block), padded width)``, and the kernel skips chunks
    past a block's cap. Dense rows cost their whole block its bucket
    (the price of skipping the load-balancing sort); the tile path has
    the same granularity, so decoded still only refines it.

    s: (T, B, L, K) spikes. Returns (cid (B, T, L, K) int32 chunk ids,
    caps (B * T * ceil(L / l_block),) int32, c_block, nc) with c_block
    clipped to K and nc the chunk count of the padded width.
    """
    t, b, l, k = s.shape
    l_block = max(1, min(l_block, l))
    nlb = -(-l // l_block)
    c_block = max(1, min(c_block, k))
    cid, occ = chunk_ids(s.reshape(t * b * l, k), c_block)
    cp = -(-k // c_block) * c_block
    occ_pad = pad_to_multiple(occ.reshape(t * b, l), 1, l_block)
    gmax = occ_pad.reshape(t * b, -1, l_block).max(axis=2)[:, :nlb]
    caps = jnp.minimum(pow2ceil(gmax), cp).astype(jnp.int32)
    cid = jnp.transpose(cid.reshape(t, b, l, k), (1, 0, 2, 3))
    caps = jnp.transpose(caps.reshape(t, b, nlb), (1, 0, 2)).reshape(-1)
    return cid, caps, c_block, cp // c_block


def quant_gather_spike_matmul(s: jax.Array, qw: jax.Array,
                              scale: jax.Array, *,
                              bias: Optional[jax.Array] = None,
                              block_m: int = 128, block_n: int = 128,
                              c_block: int = 128,
                              cap: Optional[int] = None,
                              counts: bool = False,
                              interpret: Optional[bool] = None
                              ) -> jax.Array:
    """Decoded datapath against int8 weight codes: y = (s @ qw) * scale
    (+ bias), int32 accumulation over the executed chunks, per-channel
    scale in the epilogue — the same dual-side compression as
    ``quant_spike_matmul`` at compacted-chunk granularity. ``counts=True``
    rides the left operand on int32 lanes (binary-attention counts wrap
    int8 at 128); spikes stay int8.
    """
    m, k = s.shape
    k2, n = qw.shape
    assert k == k2, f"spikes K={k} vs weight K={k2}"
    assert qw.dtype == jnp.int8, f"quant kernel wants int8 codes, got " \
        f"{qw.dtype} (unpack int4 nibbles first)"
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c_block = min(c_block, k if cap is None else max(1, cap))
    extra = [scale] + ([] if bias is None else [bias])
    return _call(functools.partial(_qkernel, bias=bias is not None),
                 s.astype(jnp.int32 if counts else jnp.int8), qw, extra,
                 block_m=block_m, block_n=block_n, c_block=c_block,
                 cap=cap, scratch=[pltpu.VMEM((block_m, block_n),
                                              jnp.int32)],
                 interpret=interpret)
