"""Dual-engine dispatch: per-matmul *and* per-attention engine selection.

FireFly-T's overlay couples a *sparse engine* (spike x weight projections,
zero-skipping) with a *binary engine* (QK^T / QK^T V, AND-PopCount). This
module is the orchestrator (DESIGN.md §3/§4) for both halves:

Sparse engine — every spiking matmul (Q/K/V/O projections, the MLP,
anything whose input is a {0,1} spike tensor) routes through
:func:`spike_linear`, which picks per call site between

  * ``dense``  — plain XLA dot, fp32 accumulation (the measurement
    baseline every perf PR compares against), and
  * ``sparse`` — one of two zero-skipping Pallas datapaths, selected by
    ``EngineConfig.sparse`` (tile | decoded, DESIGN.md §9):
    the block-sparse ``spike_matmul`` kernel skips all-zero (block_m x
    block_k) spike tiles via the occupancy map, and the rank-decoded
    ``spike_decode`` kernel sorts rows by occupancy into pow2 buckets and
    runs one masked full-K dot per executed compacted chunk, so it skips
    only whole groups of dark rows (DESIGN.md §9).

Binary engine — every spiking self-attention (``core.attention.
spiking_attention``, the transformer family's spiking SSA) consults
:func:`resolve_binary_mode` for its execution target:

  * ``jnp``        — the pure-jnp reference dataflow (scores, binarize,
    context), the baseline the kernels are pinned against;
  * ``mxu_kernel`` — the fused single-pass Pallas kernel
    (``kernels/spike_attention``): {0,1} dot products on the MXU *are*
    AND-PopCount, the L x L attention matrix never leaves VMEM;
  * ``popcount``   — the literal FPGA port (``kernels/
    popcount_attention``): spikes bit-packed 32x into uint32 lanes,
    scores via VPU ``population_count``. Kept first-class to pin the
    AND-PopCount semantics and to quantify that the MXU form dominates
    on TPU (never chosen by ``auto``).

Layer program — :func:`layer_step` / :func:`layer_step_causal` own a
whole encoder layer (the SSA bundle, output projection, residuals and
the spiking MLP). ``EngineConfig.overlap`` (off | fused) and
``EngineConfig.sparse`` (tile | decoded) are read as they are set, the
same under jit as eagerly: ``fused`` runs an eligible eval layer as one
Pallas grid (``kernels/fused_layer.py``) in which the two engines run
interleaved per head, the paper's Fig. 5 schedule made structural, and
``sparse='decoded'`` puts the compacted-chunk datapath inside it;
``off`` runs the layer's sequential oracle (``reference_layer``).

The dense/sparse and binary-engine decisions (``mode``, ``binary``) stay
*static* on shape (resolved at trace time): jit can't branch on runtime
density, so their ``auto`` uses the flop volume as the proxy — tiny
matmuls / tiny attention can't amortize kernel staging and stay on the
XLA path. The engine is installed ambiently (thread-local, like
sharding rules) by the step builders from ``ModelConfig.engine``, so
model code stays free of engine plumbing.
Off-TPU the kernels run in ``interpret`` mode — the bit-exact Python
evaluation this container's tests validate against.

Both engines carry custom VJPs (dense fp32 transposes / surrogate-
gradient recompute in bwd): spike inputs come from surrogate-gradient
LIF neurons, so training steps differentiate straight through dispatch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.scopes import annotate, disable_annotations  # noqa: F401

SPARSE_PATHS = ("tile", "decoded")
OVERLAP_MODES = ("off", "fused")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Dual-engine dispatch knobs (per model, set on ModelConfig.engine).

    Sparse engine (spike x weight matmuls):
    mode: 'dense' | 'sparse' | 'auto'. 'auto' goes sparse only when the
      matmul's flop volume clears ``min_flops`` (occupancy staging and
      per-block control flow need real work to amortize — and it keeps
      CPU smoke configs on the fast XLA path).
    sparse: 'tile' | 'decoded' — which sparse datapath a
      sparse-resolved matmul runs (DESIGN.md §9):
      - 'tile': the block-occupancy kernel (skips whole block_m x
        block_k spike tiles) — the conservative default, profitable at
        *coherent* sparsity;
      - 'decoded': the rank-decoded kernel (kernels/spike_decode.py) —
        rows sorted by occupancy into groups, pow2 bucket caps, one
        masked full-K dot per executed compacted chunk. It skips whole
        groups of dark rows, never the MACs inside a live group.
    block_*: VMEM tile sizes of the kernel; (block_m x block_k) is the
      tile path's skip granularity and block_k doubles as the decoded
      path's compacted-chunk width.

    Binary engine (spiking self-attention):
    binary: 'jnp' | 'mxu_kernel' | 'popcount' | 'auto'. 'auto' picks the
      fused MXU kernel when the attention flop volume (both matmuls,
      4 * BH * L^2 * d) clears ``min_flops``, else the jnp reference;
      'popcount' (the bit-packed VPU port) is only ever explicit — the
      benchmarks document that the MXU form dominates on TPU.
    attn_block_q / attn_block_k: KV-tile sizes of the attention kernels
      (non-divisible L is zero-padded inside the kernels).
    packed_kv: spiking decode caches store K/V bit-packed (uint32, the
      paper's 32x spike-RAM compression) and score against them with
      AND-PopCount; layout is static per config, so this lives here and
      not in the ambient state.

    overlap: 'off' | 'fused' — whether an eligible eval layer of
      layer_step/layer_step_causal runs as the fused dual-engine grid
      (kernels/fused_layer.py: projection tiles and AND-PopCount tiles
      interleaved per head, the Fig. 5 overlap made structural) or as
      its sequential oracle. Honored as set, eagerly and under jit. The
      fused grid is eval-only (train-mode BN needs global batch stats);
      train mode and layer shapes it does not cover (bias terms, mixed
      quantization, GQA, qk_norm, gated MLPs — see layer_step /
      layer_step_causal) run the sequential composition whatever this
      field says.

    weights: weight datapath dtype — 'fp32' (native params), 'int8', or
      'int4'. This is the *declared* serving datapath (launch/serve.py
      --quantize sets it and quantizes the params at load; repro.quant);
      per-call dispatch is transparent on the param dict — a quantized
      ``{"qw","scale"[,"b"]}`` dict routes through the int8-accumulating
      kernel (sparse) or the int-exact fp32 reference (dense) whatever
      this field says, so mixed trees (fp embeddings + int8 linears) just
      work.

    interpret: force Pallas interpret mode (None = auto: off-TPU only).
    """
    mode: str = "auto"
    sparse: str = "tile"
    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    min_flops: int = 1 << 22
    binary: str = "auto"
    attn_block_q: int = 128
    attn_block_k: int = 128
    packed_kv: bool = True
    overlap: str = "off"
    weights: str = "fp32"
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.weights not in ("fp32", "int8", "int4"):
            raise ValueError(f"unknown weights datapath {self.weights!r} "
                             f"(expected fp32|int8|int4)")
        if self.sparse not in SPARSE_PATHS:
            raise ValueError(f"unknown sparse datapath {self.sparse!r} "
                             f"(expected tile|decoded)")
        if self.overlap not in OVERLAP_MODES:
            raise ValueError(f"unknown overlap mode {self.overlap!r} "
                             f"(expected off|fused)")

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


DENSE = EngineConfig(mode="dense")
SPARSE = EngineConfig(mode="sparse")

_state = threading.local()


def set_engine(engine: Optional[EngineConfig]) -> None:
    _state.engine = engine


def get_engine() -> Optional[EngineConfig]:
    return getattr(_state, "engine", None)


class use_engine:
    """Context manager installing the ambient engine (mirrors
    sharding.use_rules). ``use_engine(None)`` disables dispatch."""

    def __init__(self, engine: Optional[EngineConfig]):
        self.engine = engine

    def __enter__(self):
        self.prev = get_engine()
        set_engine(self.engine)
        return self.engine

    def __exit__(self, *exc):
        set_engine(self.prev)


def engine_scope(cfg) -> contextlib.AbstractContextManager:
    """Engine context for a model config: installs ``cfg.engine`` when the
    config sets one, otherwise leaves the ambient engine untouched (so a
    caller-installed engine survives step builders for engine-less
    configs)."""
    engine = getattr(cfg, "engine", None)
    if engine is None:
        return contextlib.nullcontext()
    return use_engine(engine)


def resolve_mode(engine: Optional[EngineConfig], m: int, k: int, n: int
                 ) -> str:
    """Static dense/sparse decision for an (M, K) x (K, N) spike matmul."""
    if engine is None:
        return "dense"
    if engine.mode in ("dense", "sparse"):
        return engine.mode
    if engine.mode != "auto":
        raise ValueError(f"unknown engine mode {engine.mode!r}")
    return "sparse" if 2 * m * k * n >= engine.min_flops else "dense"


BINARY_MODES = ("jnp", "mxu_kernel", "popcount")


def resolve_binary_mode(engine: Optional[EngineConfig], bh: int, l: int,
                        d: int) -> str:
    """Static binary-engine decision for a (BH, L, d) spiking attention.

    ``bh`` is the folded batch x heads dim; the workload is two L x L x d
    matmuls per batch entry (QK^T and attn @ V — no softmax between, see
    kernels/spike_attention). 'auto' never picks 'popcount': the MXU
    kernel dominates it on TPU (DESIGN.md §3); the popcount path is an
    explicit, semantics-pinning selection.
    """
    if engine is None:
        return "jnp"
    if engine.binary in BINARY_MODES:
        return engine.binary
    if engine.binary != "auto":
        raise ValueError(f"unknown binary engine mode {engine.binary!r}")
    return "mxu_kernel" if 4 * bh * l * l * d >= engine.min_flops else "jnp"


# ---------------------------------------------------------------------------
# sparse path: Pallas kernel fwd (tile or decoded), dense-transpose bwd
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _sparse_matmul(s2d, w, b, block_m, block_n, block_k, path, interpret):
    # keep the fp32 accumulator: spike_linear casts once to the
    # activation dtype, exactly like the dense reference — a w.dtype
    # round-trip here would break bit-parity for mixed dtypes.
    if path == "decoded":
        from repro.kernels.spike_decode import gather_spike_matmul  # lazy
        return gather_spike_matmul(s2d, w, bias=b, block_m=block_m,
                                   block_n=block_n, c_block=block_k,
                                   interpret=interpret)
    from repro.kernels.spike_matmul import spike_matmul  # lazy: no cycle
    return spike_matmul(s2d, w, bias=b, block_m=block_m, block_n=block_n,
                        block_k=block_k, out_dtype=jnp.float32,
                        interpret=interpret)


def _sparse_fwd(s2d, w, b, block_m, block_n, block_k, path, interpret):
    out = _sparse_matmul(s2d, w, b, block_m, block_n, block_k, path,
                         interpret)
    return out, (s2d, w, b)


def _sparse_bwd(block_m, block_n, block_k, path, interpret, res, g):
    s2d, w, b = res
    g32 = g.astype(jnp.float32)
    ds = jnp.dot(g32, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32).astype(s2d.dtype)
    dw = jnp.dot(s2d.astype(jnp.float32).T, g32,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    db = None if b is None else g32.sum(axis=0).astype(b.dtype)
    return ds, dw, db


_sparse_matmul.defvjp(_sparse_fwd, _sparse_bwd)


# ---------------------------------------------------------------------------
# quantized sparse path: int8-accumulating Pallas kernel fwd, dequantized
# dense transposes bwd (repro.quant weight datapath, DESIGN.md §8)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _quant_sparse_matmul(s2d, qw, scale, b, block_m, block_n, block_k,
                         path, counts, interpret):
    if path == "decoded":
        from repro.kernels.spike_decode import \
            quant_gather_spike_matmul  # lazy
        return quant_gather_spike_matmul(
            s2d, qw, scale, bias=b, block_m=block_m, block_n=block_n,
            c_block=block_k, counts=counts, interpret=interpret)
    from repro.kernels.spike_matmul import quant_spike_matmul  # lazy
    return quant_spike_matmul(s2d, qw, scale, bias=b, block_m=block_m,
                              block_n=block_n, block_k=block_k,
                              counts=counts, interpret=interpret)


def _quant_sparse_fwd(s2d, qw, scale, b, block_m, block_n, block_k,
                      path, counts, interpret):
    out = _quant_sparse_matmul(s2d, qw, scale, b, block_m, block_n,
                               block_k, path, counts, interpret)
    return out, (s2d, qw, scale, b)


def _quant_sparse_bwd(block_m, block_n, block_k, path, counts, interpret,
                      res, g):
    """ds flows through the *dequantized* weights (the fp32 function the
    int kernel computes); int8 codes get a float0 cotangent (integer
    leaves are non-differentiable); scale/bias get their true grads so a
    forward under jax.grad never silently zeroes a float leaf."""
    import numpy as np
    s2d, qw, scale, b = res
    g32 = g.astype(jnp.float32)
    w_deq = qw.astype(jnp.float32) * scale[None, :]
    ds = jnp.dot(g32, w_deq.T,
                 preferred_element_type=jnp.float32).astype(s2d.dtype)
    acc = jnp.dot(s2d.astype(jnp.float32), qw.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    dscale = (g32 * acc).sum(axis=0).astype(scale.dtype)
    dqw = np.zeros(qw.shape, dtype=jax.dtypes.float0)
    db = None if b is None else g32.sum(axis=0).astype(b.dtype)
    return ds, dqw, dscale, db


_quant_sparse_matmul.defvjp(_quant_sparse_fwd, _quant_sparse_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def dense_spike_linear(p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """The dense reference: fp32-accumulated dot + bias, cast back to the
    activation dtype — term-for-term what the sparse kernel computes.

    Operands stay in their native dtype (no hoisted upcasts — bf16 feeds
    the MXU directly and the result is cast back before any collective,
    preserving the §Perf F1 bf16 traffic); only the accumulator is fp32.
    """
    y = jnp.dot(x, p["w"], preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def _unpacked_qw(p: Dict[str, Any], k: int) -> jax.Array:
    """int8 weight codes from a quantized param dict (int4 nibbles are
    unpacked to int8 at dispatch; storage stays packed)."""
    qw = p["qw"]
    if qw.dtype == jnp.uint8:
        from repro.quant.quantize import unpack_int4  # lazy: no cycle
        qw = unpack_int4(qw, k)
    return qw


def dense_quant_linear(p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """The quantized dense reference: fp32-accumulated dot against the raw
    int codes, per-output-channel scale + bias in the epilogue, cast back
    to the activation dtype.

    On {0,1} spike inputs every partial sum is a small integer held
    exactly in fp32, so this equals the int32-accumulating kernel
    bitwise; on analog inputs it is weight-only quantized compute (the
    int codes dequantize on the fly through the epilogue scale).
    """
    k = x.shape[-1]
    qw = _unpacked_qw(p, k)
    acc = jnp.dot(x, qw.astype(x.dtype),
                  preferred_element_type=jnp.float32)
    y = acc * p["scale"].astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def spike_linear(p: Dict[str, Any], x: jax.Array, *,
                 engine: Optional[EngineConfig] = None,
                 counts: bool = False) -> jax.Array:
    """Dual-engine linear layer for spike (or spike-derived sparse) inputs.

    p: {'w': (K, N)[, 'b': (N,)]} param dict (models/nn.py layout), or the
    quantized layout {'qw', 'scale'[, 'b']} (repro.quant) — quantized
    dicts route through the int8-accumulating kernel on the sparse path
    and the int-exact fp32 reference on the dense path;
    x: (..., K) activations — {0,1} spikes or the sparse integer counts a
    binary-attention context carries; the count call sites declare
    ``counts=True`` so the quantized kernel gives the left operand int32
    lanes (an int8 cast would wrap counts >= 128 — spikes stay int8, the
    MXU fast path). Leading dims fold into the sparse engine's M.
    ``engine=None`` uses the ambient engine (see use_engine); no ambient
    engine means dense.
    """
    engine = engine if engine is not None else get_engine()
    k = x.shape[-1]
    quantized = "qw" in p
    if engine is not None and engine.weights != "fp32":
        # the declared datapath is a contract, not a comment: a config
        # serving int8 must actually be handed int8 codes (catches a
        # quantize-at-load step that missed a linear, or width mismatch).
        # An int4 declaration accepts int8-dtyped codes too: the int4
        # quantizer deliberately leaves odd-K linears as int8-stored
        # 4-bit codes (quantize_weight), indistinguishable by dtype.
        ok = quantized and (engine.weights == "int4"
                            or p["qw"].dtype == jnp.int8)
        if not ok:
            actual = "fp32 (unquantized)" if not quantized \
                else "packed int4"
            raise ValueError(
                f"engine declares weights={engine.weights!r} but this "
                f"linear's params are {actual} (quantize_tree the params "
                f"or fix EngineConfig.weights)")
    n = (p["qw"] if quantized else p["w"]).shape[-1]
    m = 1
    for d in x.shape[:-1]:
        m *= d
    if resolve_mode(engine, m, k, n) == "dense":
        with annotate("sparse_engine.dense"):
            return dense_quant_linear(p, x) if quantized \
                else dense_spike_linear(p, x)
    x2d = x.reshape(-1, k)
    path = engine.sparse
    with annotate(f"sparse_engine.{path}"):
        if quantized:
            out = _quant_sparse_matmul(
                x2d.astype(jnp.float32), _unpacked_qw(p, k),
                p["scale"].astype(jnp.float32), p.get("b"),
                engine.block_m, engine.block_n, engine.block_k,
                path, counts, engine.interpret)
        else:
            out = _sparse_matmul(x2d, p["w"], p.get("b"),
                                 engine.block_m, engine.block_n,
                                 engine.block_k, path, engine.interpret)
    return out.reshape(*x.shape[:-1], n).astype(x.dtype)


# ---------------------------------------------------------------------------
# the SSA bundle, sequential: what train mode and ineligible layers run
# ---------------------------------------------------------------------------


def ssa_step(p: Dict[str, Any], st: Dict[str, Any], cfg, s: jax.Array, *,
             train: bool = False):
    """The vision-family SSA bundle (bidirectional, BN epilogues):
    projections Q/K/V (+ BatchNorm + LIF) and binary attention, composed
    from the engine's primitives (:func:`spike_linear` through
    ``nn.linear``, ``spiking_attention``). :func:`layer_step` hands the
    bundle here for train mode and ineligible layers.

    p: {'wq','wk','wv','bn_q','bn_k','bn_v','delta', ...}; st: the BN
    running-stats subtree; s: (T, B, L, D) {0,1} spikes (post input LIF);
    cfg: ModelConfig. Returns (ctx (T, B, L, q_dim), new BN state).
    """
    from repro.core.attention import spiking_attention  # lazy: no cycle
    from repro.core.spiking import lif_scan
    from repro.models import nn
    t, b, l, _ = s.shape
    heads, hd = cfg.num_heads, cfg.head_dim
    new_st = dict(st)

    def proj(name, w):
        cur = nn.linear(p[w], s, spikes=True)
        y, bn_st = nn.batchnorm(p[f"bn_{name}"], st[f"bn_{name}"],
                                cur.reshape(-1, cur.shape[-1]), train=train)
        new_st[f"bn_{name}"] = bn_st
        sp, _ = lif_scan(y.reshape(cur.shape), cfg.spiking)
        return sp

    q_s = proj("q", "wq")
    k_s = proj("k", "wk")
    v_s = proj("v", "wv")
    # (T,B,L,q_dim) -> (T*B, H, L, hd) for the binary-attention primitive
    fold = lambda u: u.reshape(t * b, l, heads, hd).transpose(0, 2, 1, 3)
    ctx = spiking_attention(fold(q_s), fold(k_s), fold(v_s), cfg.spiking,
                            delta_score=p["delta"])
    return ctx.transpose(0, 2, 1, 3).reshape(t, b, l, cfg.q_dim), new_st


def ssa_step_causal(p: Dict[str, Any], cfg, h: jax.Array, positions
                    ) -> jax.Array:
    """The token-family SSA bundle (causal, RoPE epilogues): Q/K/V
    projections (+ RoPE + LIF) and causal binary attention, composed from
    the engine's primitives. :func:`layer_step_causal` hands the bundle
    here for ineligible layers.

    h: (T, B, S, D) normed membrane currents (post ln1); positions: (S,).
    Returns attn (T, B, S, q_dim) — pre-wo context.
    """
    from repro.core.attention import spiking_attention  # lazy: no cycle
    from repro.core.spiking import lif_scan
    from repro.models.transformer import _project_qkv  # lazy: no cycle
    t, b, s_len, _ = h.shape
    q, k, v = _project_qkv(p, cfg, h, positions, repeat_kv=True)
    q, k, v = (lif_scan(u, cfg.spiking)[0] for u in (q, k, v))
    fold = lambda u: u.reshape(-1, *u.shape[2:])     # (T*B, S, H, hd)
    swap = lambda u: u.transpose(0, 2, 1, 3)
    ctx = spiking_attention(swap(fold(q)), swap(fold(k)), swap(fold(v)),
                            cfg.spiking, delta_score=p["delta"],
                            causal=True)
    return swap(ctx).reshape(t, b, s_len, cfg.q_dim)


# ---------------------------------------------------------------------------
# the layer program: SSA bundle + output projection + residuals + spiking
# MLP as one engine step. overlap='fused' runs it as one Pallas grid
# (kernels/fused_layer.py) with the decoded gather datapath available
# inside the projection phases and a per-phase occupancy map for the
# binary engine; overlap='off' runs its sequential oracle. Custom VJP
# recomputes the sequential oracle in bwd.
# ---------------------------------------------------------------------------


class _LayerSpec(NamedTuple):
    """Static (hashable) closure of a layer-program step — the nondiff
    arg of the custom VJP, shared verbatim by the fwd (kernel or oracle)
    and the oracle bwd."""
    family: str
    num_heads: int
    head_dim: int
    scale: float
    causal: bool
    scfg: Any                   # SpikingConfig (frozen dataclass)
    eps: float
    norm_eps: float
    overlap: str                # off | fused
    sparse: str                 # tile | decoded
    l_block: int
    c_block: int
    interpret: Optional[bool]


def _layer_kernel_args(ops, spec):
    return ((ops["x"], ops["s"], ops["w3"], ops["wo"], ops["w1"],
             ops["w2"], ops["scales"], ops["auxp"], ops["auxo"],
             ops["aux1"], ops["aux2"], ops["delta"]),
            dict(family=spec.family, num_heads=spec.num_heads,
                 head_dim=spec.head_dim, scale=spec.scale,
                 causal=spec.causal, eps=spec.eps,
                 norm_eps=spec.norm_eps))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused_layer(ops, spec):
    """Every *eligible* layer runs through this step — also with
    ``overlap='off'``, where the fwd is the sequential oracle itself.
    One function means one gradient program for both overlap modes (the
    same bwd jaxpr below), which is what makes off and fused gradients
    bitwise-identical *by construction*: an inline-autodiff
    bwd and a recompute bwd are different jaxprs computing the same
    math, and XLA's FMA contraction resolves them differently at the
    one-ulp level once the layer scan splits fwd and bwd into separate
    compiled programs."""
    if spec.overlap == "off":
        from repro.kernels.fused_layer import reference_layer  # lazy
        args, kw = _layer_kernel_args(ops, spec)
        return reference_layer(*args, spec.scfg, **kw)
    from repro.kernels.fused_layer import fused_layer  # lazy: no cycle
    args, kw = _layer_kernel_args(ops, spec)
    out, _ = fused_layer(
        *args, sparse=spec.sparse,
        binarize_scores=spec.scfg.binarize_scores, decay=spec.scfg.decay,
        v_th=spec.scfg.v_threshold, soft_reset=spec.scfg.soft_reset,
        l_block=spec.l_block, c_block=spec.c_block,
        interpret=spec.interpret, **kw)
    return out


def _layer_fwd(ops, spec):
    return _fused_layer(ops, spec), ops


def _layer_bwd(spec, res, g):
    """Recompute-through-the-oracle bwd: differentiate
    ``kernels.fused_layer.reference_layer`` — the sequential layer
    composition the kernel is pinned against bitwise — so the fused path
    returns exactly the sequential path's gradients, surrogate LIF /
    binarize jvps included. Quantized int codes are cast to the
    activation dtype before this boundary; d_ff zero-padding happens
    outside it, so pad cotangents slice back automatically."""
    from repro.kernels.fused_layer import reference_layer  # lazy

    def f(o):
        args, kw = _layer_kernel_args(o, spec)
        return reference_layer(*args, spec.scfg, **kw)

    _, vjp = jax.vjp(f, res)
    return vjp(g)


_fused_layer.defvjp(_layer_fwd, _layer_bwd)


def _layer_quant_w3(p, names, d, dtype):
    """(stacked qkv weights, per-proj scales) for an all-quantized layer."""
    w3 = jnp.stack([_unpacked_qw(p[w], d) for w in names]).astype(dtype)
    scale3 = jnp.stack([p[w]["scale"].astype(jnp.float32) for w in names])
    return w3, scale3


def _layer_linear(p, k, dtype):
    """(weight codes cast to activation dtype, fp32 scale-or-ones) for one
    layer linear — quantized or native."""
    if "qw" in p:
        return _unpacked_qw(p, k).astype(dtype), \
            p["scale"].astype(jnp.float32)
    return p["w"], jnp.ones((p["w"].shape[-1],), jnp.float32)


def _pad_ff(w1, w2, sc1, aux1, heads):
    """Zero-pad d_ff to a multiple of ``num_heads`` (the fused grid hands
    each head one ff-chunk). Exact: padded up-columns are zero, so the
    padded channels carry zero current, normalize to zero through the
    identity BN rows appended to aux1 ([mean 0, var 1, scale 1, bias 0]),
    never cross the LIF threshold (v_th > 0), and meet zero down-rows."""
    ff = w1.shape[1]
    pad = (-ff) % heads
    if pad == 0:
        return w1, w2, sc1, aux1
    w1 = jnp.pad(w1, ((0, 0), (0, pad)))
    w2 = jnp.pad(w2, ((0, pad), (0, 0)))
    sc1 = jnp.pad(sc1, (0, pad), constant_values=1.0)
    if aux1 is not None:
        ident = jnp.tile(jnp.asarray([0.0, 1.0, 1.0, 0.0],
                                     jnp.float32)[:, None], (1, pad))
        aux1 = jnp.concatenate([aux1, ident], axis=1)
    return w1, w2, sc1, aux1


def _layer_spec(engine, family, cfg, causal, norm_eps):
    """The layer program's static plan: the engine's overlap and sparse
    datapath as set, and its block sizes (no engine: the defaults)."""
    eng = engine if engine is not None else EngineConfig()
    hd = cfg.head_dim
    return _LayerSpec(family, cfg.num_heads, hd, 1.0 / math.sqrt(hd),
                      causal, cfg.spiking, 1e-5, norm_eps, eng.overlap,
                      eng.sparse, eng.block_m, eng.block_k, eng.interpret)


def _bn_rows(p, st, name):
    return jnp.stack([st[name]["mean"].astype(jnp.float32),
                      st[name]["var"].astype(jnp.float32),
                      p[name]["scale"].astype(jnp.float32),
                      p[name]["bias"].astype(jnp.float32)])


def layer_step(p: Dict[str, Any], st: Dict[str, Any], cfg, x: jax.Array,
               *, train: bool = False,
               engine: Optional[EngineConfig] = None):
    """The vision-family *layer program*: input LIF + SSA bundle + output
    projection (wo + bn_o) + pre-neuron residual + spiking MLP (w1 +
    bn_1 + LIF + w2 + bn_2) + residual, as one engine-owned step.
    ``models/spikingformer._block`` hands the whole encoder layer here.

    p/st: the block param/state subtrees (_block_init/_block_state
    layout); x: (T, B, L, D) membrane currents (the residual stream);
    cfg: ModelConfig. Returns (y (T, B, L, D), new BN state).

    The plan is the engine's ``overlap`` and ``sparse`` as set (no
    engine: off, tile). An eligible layer — eval only (train-mode BN
    needs global batch stats), bias-free linears, all-or-none
    quantization, binarized scores with analog context (the blocked
    binary phases and the head-split wo contraction stay exact on
    integer contexts) — routes through the shared custom-VJP step:
    ``overlap='fused'`` runs the whole program as one Pallas grid
    (kernels/fused_layer.py, with ``sparse='decoded'`` composing the
    gather-compacted projection datapath into the fused phases), and
    ``overlap='off'`` runs the sequential oracle ``reference_layer`` as
    its fwd, so both agree bitwise on gradients by construction (see
    ``_fused_layer``). Ineligible layers (and train mode) run the plain
    sequential composition below, with the SSA bundle in
    :func:`ssa_step`.
    """
    engine = engine if engine is not None else get_engine()
    from repro.core.spiking import lif_scan
    from repro.models import nn
    t, b, l, d = x.shape
    heads, hd = cfg.num_heads, cfg.head_dim
    lin_names = ("wq", "wk", "wv", "wo", "w1", "w2")
    quant = ["qw" in p[w] for w in lin_names]
    eligible = (not train
                and (all(quant) or not any(quant))
                and not any("b" in p[w] for w in lin_names)
                and cfg.spiking.binarize_scores
                and not cfg.spiking.binarize_context)
    s = lif_scan(x, cfg.spiking)[0]
    if eligible:
        dtype = x.dtype
        if all(quant):
            w3, sc3 = _layer_quant_w3(p, ("wq", "wk", "wv"), d, dtype)
        else:
            w3 = jnp.stack([p[w]["w"] for w in ("wq", "wk", "wv")])
            sc3 = jnp.ones((3, cfg.q_dim), jnp.float32)
        wo, sco = _layer_linear(p["wo"], cfg.q_dim, dtype)
        w1, sc1 = _layer_linear(p["w1"], d, dtype)
        w2, sc2 = _layer_linear(p["w2"], cfg.d_ff, dtype)
        aux1 = _bn_rows(p, st, "bn_1")
        w1, w2, sc1, aux1 = _pad_ff(w1, w2, sc1, aux1, heads)
        ops = {
            "x": x, "s": s, "w3": w3, "wo": wo, "w1": w1, "w2": w2,
            "scales": (sc3, sco, sc1, sc2),
            "auxp": jnp.stack([_bn_rows(p, st, f"bn_{n}")
                               for n in ("q", "k", "v")]),
            "auxo": _bn_rows(p, st, "bn_o"),
            "aux1": aux1, "aux2": _bn_rows(p, st, "bn_2"),
            "delta": p["delta"],
        }
        spec = _layer_spec(engine, "bn", cfg, False, 1e-6)
        with annotate("dual_engine.fused_layer"):
            y = _fused_layer(ops, spec)
        return y, dict(st)
    # sequential composition (what models/spikingformer._block used to
    # inline): train mode and ineligible layers
    ctx, new_st = ssa_step(p, {n: st[n] for n in ("bn_q", "bn_k", "bn_v")},
                           cfg, s, train=train)
    new_st = dict(st, **new_st)
    # ctx is binarized-attention output: sparse integer counts, not {0,1}
    # spikes — but zero blocks are zero blocks, so the sparse engine
    # skips them all the same. counts=True: under quantized weights the
    # counts (up to L) must ride int32 lanes, not the spikes' int8 path.
    out = nn.linear(p["wo"], ctx, spikes=True, counts=True)
    out, bn_st = nn.batchnorm(p["bn_o"], st["bn_o"],
                              out.reshape(-1, d), train=train)
    new_st["bn_o"] = bn_st
    x = x + out.reshape(t, b, l, d)               # pre-neuron residual
    s2 = lif_scan(x, cfg.spiking)[0]
    h = nn.linear(p["w1"], s2, spikes=True)
    h, bn1 = nn.batchnorm(p["bn_1"], st["bn_1"],
                          h.reshape(-1, h.shape[-1]), train=train)
    new_st["bn_1"] = bn1
    h = lif_scan(h.reshape(t, b, l, cfg.d_ff), cfg.spiking)[0]
    o = nn.linear(p["w2"], h, spikes=True)
    o, bn2 = nn.batchnorm(p["bn_2"], st["bn_2"],
                          o.reshape(-1, o.shape[-1]), train=train)
    new_st["bn_2"] = bn2
    return x + o.reshape(x.shape), new_st         # pre-neuron residual


def layer_step_causal(p: Dict[str, Any], cfg, x: jax.Array, positions, *,
                      engine: Optional[EngineConfig] = None) -> jax.Array:
    """The token-family *layer program* (causal, RoPE/rmsnorm epilogues):
    ln1 + SSA bundle + wo + residual + ln2 + spiking MLP + residual as
    one engine-owned step — the spiking full-attention branch of
    ``models/transformer.apply_layer`` hands the whole layer here.

    x: (T, B, S, D) residual-stream currents; positions: (S,). Returns
    the new residual stream (T, B, S, D).

    Fused eligibility = the bundle's (no qk_norm, no GQA, bias-free,
    all-or-none quantization, even head_dim, 1-D positions, fp32
    activations unless quantized) plus the MLP tail's: a plain
    (up, down) MLP — a gated MLP has no fused phase mapping — and
    binarized scores with analog context (integer contexts keep the
    head-split wo and the blocked binary phases exact). The plan is
    read from the engine as set, as in :func:`layer_step`. Eligible
    layers route through the shared custom-VJP step for both overlap
    modes (``off`` runs the sequential oracle as its fwd — one gradient
    program, see ``_fused_layer``); ineligible layers fall back to the
    plain sequential composition, with the bundle in
    :func:`ssa_step_causal`.
    """
    engine = engine if engine is not None else get_engine()
    from repro.core.spiking import lif_scan
    from repro.models import nn
    from repro.parallel.sharding import constrain
    t, b, s_len, d = x.shape
    heads, hd = cfg.num_heads, cfg.head_dim
    h = nn.rmsnorm(p["ln1"], x, cfg.norm_eps)
    lin_ps = [p["wq"], p["wk"], p["wv"], p["wo"],
              p["mlp"].get("up"), p["mlp"].get("down")]
    quant = ["qw" in q for q in lin_ps if q is not None]
    positions = jnp.asarray(positions)
    eligible = (not cfg.qk_norm
                and cfg.num_kv_heads == cfg.num_heads
                and set(p["mlp"]) == {"up", "down"}
                and (all(quant) or not any(quant))
                and not any(q is not None and "b" in q for q in lin_ps)
                and (all(quant) or x.dtype == jnp.float32)
                and hd % 2 == 0
                and positions.ndim == 1
                and cfg.spiking.binarize_scores
                and not cfg.spiking.binarize_context)
    if eligible:
        dtype = x.dtype
        if all(quant):
            w3, sc3 = _layer_quant_w3(p, ("wq", "wk", "wv"), d, dtype)
        else:
            w3 = jnp.stack([p[w]["w"] for w in ("wq", "wk", "wv")])
            sc3 = jnp.ones((3, cfg.q_dim), jnp.float32)
        wo, sco = _layer_linear(p["wo"], cfg.q_dim, dtype)
        w1, sc1 = _layer_linear(p["mlp"]["up"], d, dtype)
        w2, sc2 = _layer_linear(p["mlp"]["down"], w1.shape[1], dtype)
        w1, w2, sc1, _ = _pad_ff(w1, w2, sc1, None, heads)
        half = hd // 2
        # nn.rope's table, verbatim (same f32 expression -> same values)
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[:, None] * freqs
        ops = {
            "x": x, "s": h, "w3": w3, "wo": wo, "w1": w1, "w2": w2,
            "scales": (sc3, sco, sc1, sc2),
            "auxp": jnp.stack([jnp.cos(ang), jnp.sin(ang)]),
            "auxo": p["ln2"]["scale"].astype(jnp.float32).reshape(1, d),
            "aux1": None, "aux2": None,
            "delta": p["delta"],
        }
        spec = _layer_spec(engine, "rope", cfg, True, cfg.norm_eps)
        with annotate("dual_engine.fused_layer"):
            y = _fused_layer(ops, spec)
        return constrain(y, "batch", "seq", "embed")
    # sequential composition (what models/transformer.apply_layer used
    # to inline for the spiking full-attention branch)
    attn = ssa_step_causal(p, cfg, h, positions)
    attn = constrain(attn, "batch", "seq", "model")
    x = x + nn.linear(p["wo"], attn)
    h2 = nn.rmsnorm(p["ln2"], x, cfg.norm_eps)
    up = nn.linear(p["mlp"]["up"], h2)
    hidden = lif_scan(up, cfg.spiking)[0]
    x = x + nn.linear(p["mlp"]["down"], hidden)
    return constrain(x, "batch", "seq", "embed")
