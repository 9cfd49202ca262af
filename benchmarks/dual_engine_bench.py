"""Dual-engine sweep: both halves of the overlay.

Sparse engine (``rows``): dense XLA dot vs occupancy-skipping sparse
kernel. For each (sparsity, block, shape) point this times
``spike_linear``'s two dispatch targets on the same spike tensor and
records

  * dense_us / sparse_us — wall time per call (median of reps). On CPU
    the kernel runs in Pallas *interpret* mode, so the wall-clock ratio
    measures the lowered-lax emulation, not MXU tiles — the number that
    transfers to TPU is ``modeled_speedup``;
  * skip_fraction — fraction of (block_m x block_k) spike tiles whose
    occupancy bit is 0 (the sparse engine skips them: no weight fetch,
    no MACs);
  * modeled_speedup — 1 / (1 - skip_fraction), the MAC-count reduction
    the occupancy map guarantees on any backend.

Spikes are generated with *coherent* tile sparsity (Observation 1: spike
sparsity is uniform across the spatial-temporal grid, so channel blocks
go dark together): ``sparsity`` is the fraction of dead tiles; live
tiles fire at 25% density. That is the regime where whole-tile skips
pay; i.i.d. Bernoulli sparsity at the same rate almost never yields an
empty 128x128 tile and is reported by the bench as skip_fraction ~ 0.

Sparse datapaths (``sparse_path_rows``): tile vs decoded
(``EngineConfig.sparse``, DESIGN.md §9) on *fine-grained / ragged*
spike patterns — the regime where whole-tile skips never fire
(``skip_fraction ~ 0``). Each executed decoded chunk is a masked full-K
dot, so the decoded kernel saves only the row groups its occupancy sort
leaves all dark. Each row records both paths' wall time, the tile skip
fraction, the decoded schedule's MAC fraction (executed full-K chunk
dots per row group, ``spike_decode.decoded_dot_fraction``), and the
cross-validation of
``sim/balance_sim.predicted_schedule`` (Binomial occupancies from the
generator's density model) against the measured tensor schedule
(``kernels/spike_decode.build_schedule``) — ``sched_agreement`` is
predicted/measured executed steps. ``auto_choice`` is what
``sparse='auto'`` would pick from the concrete histogram.

Binary engine (``attention_rows``): the three SSA execution targets of
``core.engine.resolve_binary_mode`` — pure jnp, the fused MXU Pallas
kernel, the bit-packed popcount port — swept over L x d_head x causal on
identical spike tensors. All three are bit-identical (pinned by
tests/test_binary_engine.py); the sweep quantifies the *speed* gap the
dispatch rules encode (DESIGN.md §3: MXU dominates popcount on TPU). On
CPU the kernels run in interpret mode, so kernel wall-clock measures the
lowered-lax emulation — jnp_us is the transferable baseline there.

The measured medians also feed the overlap model: ``derived
['measured_overlap']`` runs ``core.dual_engine.measured_schedule`` on
(sparse_us, mxu_us) — the Fig. 5 latency-hiding fraction from measured
engine timings instead of the analytic MAC model.

Fused layer step (``fused_rows``): the ``overlap='fused'`` bundle
(``kernels/fused_ssa``) on the three spikingformer-shaped SSA
workloads. Each row feeds the kernel's per-head executed-step counts to
``core.dual_engine.fused_step_metrics``, so ``hidden_fraction`` here is
*measured* — derived from the dots the kernel actually ran (dark spike
slabs skipped, attention pipelined behind the next head's projections)
with exact per-dot MAC weights — not the analytic model. Those counts
are deterministic for the fixed PRNG inputs, so CI gates them
(``benchmarks/check_regression.py``); ``fused_us``/``sequential_us``
are interpret-mode wall clock on CPU and stay informative-only.

Layer-program step (``layer_rows``): the whole encoder layer — SSA
bundle + output projection + spiking MLP — as one engine step
(``kernels/fused_layer`` behind ``core.engine.layer_step``), swept over
``overlap in {off, fused, pipeline}`` x ``sparse in {tile, decoded}``
on the same three spikingformer workloads. Each row feeds the kernel's
``(H, 8, n_l_blocks)`` occupancy map to ``fused_step_metrics``:
``hidden_fraction`` here is the *binary-hidden* fraction — the share of
the binary engine's executed attention MACs that ride under sparse-
engine busy time in the measured schedule. The layer program's MLP
phases keep the sparse engine saturated past the SSA bundle's horizon,
which is exactly why it beats the bundle-only ``fused_rows`` number —
the CI floor on the token config (``check_regression.FLOORS``) pins
that claim. Rows also cross-validate ``sim/balance_sim
.binary_block_schedule`` (the numpy twin of the binary-phase occupancy
predicate) sub-block-exact against the measured counts.

Output: ``artifacts/dual_engine_bench.json`` in the benchmark harness's
``{"rows": [...], "attention_rows": [...], "sparse_path_rows": [...],
"fused_rows": [...], "layer_rows": [...], "derived": {...}}`` format
(also wired into ``benchmarks/run.py``, which re-emits the same file).

Usage: PYTHONPATH=src python benchmarks/dual_engine_bench.py [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

SHAPES = [(256, 128, 256), (512, 256, 256), (1024, 256, 512)]  # (M, K, N)
BLOCKS = [64, 128]
SPARSITIES = [0.5, 0.75, 0.9]
REPS = 5

# binary-engine sweep: (BH, L, d_head); 100 is deliberately non-divisible
# by the 128 attention blocks (exercises the kernels' zero-padding)
ATTN_SHAPES = [(8, 64, 32), (8, 100, 64), (8, 256, 64)]
ATTN_CAUSAL = [False, True]
ATTN_DENSITY = 0.25


def coherent_spikes(key, m, k, block, sparsity, density=0.25):
    """{0,1} (M, K) with ``sparsity`` fraction of (block x block) dead
    tiles; live tiles fire i.i.d. at ``density``."""
    k1, k2 = jax.random.split(key)
    nm, nk = -(-m // block), -(-k // block)
    live = jax.random.uniform(k1, (nm, nk)) >= sparsity
    tile_mask = jnp.repeat(jnp.repeat(live, block, 0), block, 1)[:m, :k]
    fire = jax.random.uniform(k2, (m, k)) < density
    return (tile_mask & fire).astype(jnp.float32)


def ragged_spikes(key, m, k, lo, hi):
    """{0,1} (M, K) with per-row i.i.d. firing at a log-uniform density
    in [lo, hi] — ragged occupancy, no tile coherence (the FireFly-S
    fine-grained regime the tile skip can't touch). Returns (spikes,
    per-row densities) so the bench can feed the density model to
    ``sim/balance_sim.predicted_schedule``."""
    k1, k2 = jax.random.split(key)
    logd = jax.random.uniform(k1, (m,), minval=jnp.log(lo),
                              maxval=jnp.log(hi))
    dens = jnp.exp(logd)
    s = (jax.random.uniform(k2, (m, k)) < dens[:, None])
    return s.astype(jnp.float32), dens


def fine_spikes(key, m, k, density):
    """{0,1} (M, K) i.i.d. Bernoulli — uniform fine-grained firing."""
    s = (jax.random.uniform(key, (m, k)) < density).astype(jnp.float32)
    return s, jnp.full((m,), density)


# sparse-datapath sweep: (pattern name, generator kwargs); two ragged
# patterns plus the uniform fine-grained point, all tile-incoherent
SPARSE_PATTERNS = [
    ("fine_iid", lambda key, m, k: fine_spikes(key, m, k, 0.10)),
    ("ragged_mild", lambda key, m, k: ragged_spikes(key, m, k, 0.02, 0.3)),
    ("ragged_extreme", lambda key, m, k: ragged_spikes(key, m, k,
                                                       0.005, 0.6)),
]
SPARSE_PATH_SHAPES = [(512, 256, 256), (1024, 256, 512)]
SPARSE_PATH_BLOCK = 64  # block_m/block_n; block_k doubles as c_block

# fused-step workloads: (name, family, T, B, L, D, heads, head_dim,
# causal) — the SSA shapes of the three spikingformer configs. The two
# vision points are projection-dominated (3D >> 2L: little to hide);
# the token point has L == D, where attention is 2/5 of the serial work
# and the head pipeline hides most of it (hidden_fraction ~ 0.4).
FUSED_CONFIGS = [
    ("spikingformer-4-256", "bn", 4, 2, 64, 256, 8, 32, False),
    ("spikingformer-8-512", "bn", 4, 1, 64, 512, 8, 64, False),
    ("spikingformer-lm", "rope", 4, 1, 256, 256, 4, 64, True),
]
FUSED_DENSITY = 0.25

# layer-program sweep (layer_rows): block sizes for the whole-layer
# occupancy map and the decoded projection path; d_ff = 4 * d_model
# (the spikingformer MLP ratio). Modes: the off row is the sequential
# oracle baseline; decoded rows only exist for the spike-driven (bn)
# family — the token family's ln1-normed currents are dense.
LAYER_L_BLOCK = 32
LAYER_C_BLOCK = 64
LAYER_MODES = [("off", "tile"), ("fused", "tile"), ("fused", "decoded"),
               ("pipeline", "tile"), ("pipeline", "decoded")]


def _time(fn, *args) -> float:
    fn(*args).block_until_ready()           # compile + warm
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e6   # median, us


def attention_bench(fast: bool = False):
    """Binary-engine sweep: jnp vs MXU kernel vs popcount per SSA shape."""
    from repro.core import engine as E
    from repro.core.attention import spiking_attention
    from repro.core.spiking import SpikingConfig

    scfg = SpikingConfig()
    shapes = ATTN_SHAPES[:2] if fast else ATTN_SHAPES
    rows = []
    for bh, l, d in shapes:
        ks = jax.random.split(jax.random.PRNGKey(bh + l + d), 3)
        q, k, v = ((jax.random.uniform(kk, (bh, l, d)) < ATTN_DENSITY)
                   .astype(jnp.float32) for kk in ks)
        for causal in ATTN_CAUSAL:
            us = {}
            for mode in ("jnp", "mxu_kernel", "popcount"):
                eng = E.EngineConfig(binary=mode)

                def call(q, k, v, eng=eng, causal=causal):
                    return spiking_attention(q, k, v, scfg,
                                             delta_score=0.3,
                                             causal=causal, engine=eng)
                us[mode] = _time(jax.jit(call), q, k, v)
            rows.append({
                "bench": "attention", "shape": [bh, l, d],
                "causal": causal,
                "jnp_us": round(us["jnp"], 1),
                "mxu_us": round(us["mxu_kernel"], 1),
                "popcount_us": round(us["popcount"], 1),
                "mxu_vs_jnp": round(us["jnp"] / us["mxu_kernel"], 3),
                "popcount_vs_mxu": round(
                    us["popcount"] / us["mxu_kernel"], 3),
            })
    return rows


def sparse_path_bench(fast: bool = False):
    """Tile vs decoded datapath on fine-grained / ragged spike patterns,
    plus the sim-vs-measured bucket-schedule cross-validation."""
    import numpy as np

    from repro.core import engine as E
    from repro.kernels.spike_decode import (build_schedule,
                                            choose_sparse_path,
                                            decoded_dot_fraction)
    from repro.kernels.spike_matmul import block_occupancy
    from repro.sim.balance_sim import predicted_schedule

    shapes = SPARSE_PATH_SHAPES[:1] if fast else SPARSE_PATH_SHAPES
    block = SPARSE_PATH_BLOCK
    rows = []
    for m, k, n in shapes:
        for pat_name, gen in SPARSE_PATTERNS:
            # deterministic across processes (str hash() is salted)
            key = jax.random.PRNGKey(m + k + n + sum(map(ord, pat_name)))
            kw, ks = jax.random.split(key)
            s, dens = gen(ks, m, k)
            w = jax.random.normal(kw, (k, n), jnp.float32)
            p = {"w": w}
            tile_eng = E.EngineConfig(mode="sparse", sparse="tile",
                                      block_m=block, block_n=block,
                                      block_k=block)
            dec_eng = tile_eng.replace(sparse="decoded")
            dense_us = _time(jax.jit(
                lambda s, p=p: E.spike_linear(p, s, engine=E.DENSE)), s)
            tile_us = _time(jax.jit(
                lambda s, p=p, e=tile_eng: E.spike_linear(p, s,
                                                          engine=e)), s)
            dec_us = _time(jax.jit(
                lambda s, p=p, e=dec_eng: E.spike_linear(p, s,
                                                         engine=e)), s)
            occ_tiles = block_occupancy(s, block, block)
            tile_skip = float(1.0 - occ_tiles.mean())
            occ_rows = (s != 0).sum(-1).astype(jnp.int32)
            meas = build_schedule(occ_rows, block, block, cap=k)
            dec_frac = decoded_dot_fraction(meas)
            pred = predicted_schedule(m, k, np.asarray(dens), block,
                                      block, np.random.default_rng(0))
            rows.append({
                "bench": "sparse_path", "pattern": pat_name,
                "shape": [m, k, n], "block": block,
                "measured_sparsity": float(1.0 - s.mean()),
                "dense_us": round(dense_us, 1),
                "tile_us": round(tile_us, 1),
                "decoded_us": round(dec_us, 1),
                "tile_skip_fraction": round(tile_skip, 4),
                "tile_modeled_speedup": round(
                    1.0 / max(1e-9, 1.0 - tile_skip), 3),
                "decoded_mac_fraction": round(dec_frac, 4),
                "decoded_mac_reduction": round(1.0 - dec_frac, 4),
                "decoded_modeled_speedup": round(
                    1.0 / max(1e-9, dec_frac), 3),
                "sched_measured_steps": int(meas["executed"]),
                "sched_predicted_steps": int(pred["executed"]),
                "sched_agreement": round(
                    int(pred["executed"]) / max(1, int(meas["executed"])),
                    3),
                "auto_choice": choose_sparse_path(s, block, block),
            })
    return rows


def fused_bench(fast: bool = False):
    """Fused SSA layer step on the spikingformer-shaped workloads: the
    kernel's executed-step counts -> measured Fig. 5 schedule. All three
    configs run even under ``--fast`` — the counts are what CI gates,
    and the token config is the one whose measured hidden fraction
    demonstrates the overlap (the sweep is three kernel calls, cheap
    even in interpret mode)."""
    del fast
    from repro.core import dual_engine as de
    from repro.core.spiking import SpikingConfig
    from repro.kernels.fused_ssa import fused_ssa, reference_bundle

    scfg = SpikingConfig()
    delta = 0.3
    rows = []
    for name, fam, t, b, l, d, heads, hd, causal in FUSED_CONFIGS:
        q_dim = heads * hd
        # deterministic across processes (str hash() is salted)
        key = jax.random.PRNGKey(t + b + l + d + sum(map(ord, name)))
        kx, kw, ka = jax.random.split(key, 3)
        x = (jax.random.uniform(kx, (t, b, l, d)) < FUSED_DENSITY
             ).astype(jnp.float32)
        # silent warm-up: LIF membranes start discharged, so the first
        # timestep of a sequence often fires nothing — model it with one
        # all-dark (t=0, b=0) slab the occupancy skip can measurably elide
        x = x.at[0, 0].set(0.0)
        w3 = jax.random.normal(kw, (3, d, q_dim), jnp.float32) * d ** -0.5
        if fam == "bn":
            sc, bi = jax.random.split(ka)
            aux = jnp.stack([
                jnp.zeros((q_dim,)), jnp.ones((q_dim,)),
                1.0 + 0.1 * jax.random.normal(sc, (q_dim,)),
                0.1 * jax.random.normal(bi, (q_dim,))])
            aux = jnp.broadcast_to(aux, (3, 4, q_dim))
        else:  # rope: cos/sin table for positions 0..L-1 (theta 1e4)
            half = hd // 2
            freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32)
                                / half)
            ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs
            aux = jnp.stack([jnp.cos(ang), jnp.sin(ang)])
        kw_args = dict(family=fam, num_heads=heads, head_dim=hd,
                       scale=hd ** -0.5, causal=causal)

        def fused_call(x, w3=w3, aux=aux, kw_args=kw_args):
            return fused_ssa(x, w3, None, aux, delta, **kw_args)[0]

        def seq_call(x, w3=w3, aux=aux, kw_args=kw_args):
            return reference_bundle(x, w3, None, aux, delta, scfg,
                                    **kw_args)
        fused_us = _time(jax.jit(fused_call), x)
        seq_us = _time(jax.jit(seq_call), x)
        _, counts = fused_ssa(x, w3, None, aux, delta, **kw_args)
        m = de.fused_step_metrics(counts, seq=l, k_dim=d, head_dim=hd,
                                  t_steps=t, batch=b)
        rows.append({
            "bench": "fused", "config": name, "family": fam,
            "shape": [t, b, l, d, heads, hd], "causal": causal,
            "fused_us": round(fused_us, 1),
            "sequential_us": round(seq_us, 1),
            # interpret-mode emulation on CPU — informative, never gated
            "wall_ratio": round(seq_us / fused_us, 3),
            "hidden_fraction": round(m["hidden_fraction"], 4),
            "sparse_util": round(m["sparse_util"], 4),
            "binary_util": round(m["binary_util"], 4),
            "executed_q": m["executed_q"], "executed_k": m["executed_k"],
            "executed_v": m["executed_v"],
            "executed_attn": m["executed_attn"],
            "possible_steps": m["possible_steps"],
            "executed_steps": m["executed_steps"],
            "step_reduction": round(m["step_reduction"], 4),
            "proj_skip_fraction": round(m["proj_skip_fraction"], 4),
        })
    return rows


def _dyadic(key, shape, sc):
    """Dyadic-grid weights (multiples of 2^-8): binary-spike x dyadic
    dots accumulate exactly in fp32, so the layer's internal LIF
    thresholds sit away from rounding boundaries and the sim twin's
    jitted spike recompute lands bit-identical to the kernel's."""
    return jnp.round(jax.random.normal(key, shape, jnp.float32)
                     * sc * 256) / 256


def _layer_workload(name, fam, t, b, l, d, heads, hd):
    """Whole-layer operands in the raw kernel layout (the same tensors
    ``core.engine.layer_step`` hands ``kernels/fused_layer``), fp-native
    (scales=None), with the fused_bench dark (t=0, b=0) slab."""
    from repro.core.spiking import SpikingConfig, lif_scan
    q_dim, ff = heads * hd, 4 * d
    key = jax.random.PRNGKey(t + b + l + d + sum(map(ord, name)) + 7)
    kx, kw, ka, k1, k2, ko = jax.random.split(key, 6)
    x = (jax.random.uniform(kx, (t, b, l, d)) < FUSED_DENSITY
         ).astype(jnp.float32)
    x = x.at[0, 0].set(0.0)
    w3 = _dyadic(kw, (3, d, q_dim), d ** -0.5)
    wo = _dyadic(ko, (q_dim, d), q_dim ** -0.5)
    w1 = _dyadic(k1, (d, ff), d ** -0.5)
    w2 = _dyadic(k2, (ff, d), ff ** -0.5)
    if fam == "bn":
        def rows(k, n):
            a, b2 = jax.random.split(k)
            return jnp.stack([jnp.zeros((n,)), jnp.ones((n,)),
                              1.0 + 0.1 * jax.random.normal(a, (n,)),
                              0.1 * jax.random.normal(b2, (n,))])
        ks = jax.random.split(ka, 6)
        auxp = jnp.stack([rows(k, q_dim) for k in ks[:3]])
        auxo, aux1, aux2 = (rows(ks[3], d), rows(ks[4], ff),
                            rows(ks[5], d))
        s = lif_scan(x, SpikingConfig())[0]         # spikes feed q/k/v
    else:  # rope: cos/sin tables; s is the ln1-normed residual stream
        half = hd // 2
        freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32)
                            / half)
        ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs
        auxp = jnp.stack([jnp.cos(ang), jnp.sin(ang)])
        auxo = jnp.ones((1, d), jnp.float32)        # ln2 rmsnorm scale
        aux1 = aux2 = None
        x32 = x.astype(jnp.float32)
        s = (x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
        ).astype(x.dtype)
    return (x, s, w3, wo, w1, w2, None, auxp, auxo, aux1, aux2), ff


def _proj_kv_spikes(s, w3, auxp, fam, heads, hd):
    """K/V projection spikes as the fused kernel's projection phases
    emit them — the measured side of the ``binary_block_schedule`` sim
    cross-validation. Jitted: the kernel body is always compiled, and
    compiled dots FMA-contract, so an eager recompute could flip a
    threshold-boundary spike."""
    from repro.core.spiking import SpikingConfig, lif_scan

    @jax.jit
    def f(s, w3, auxp):
        out = []
        for i, roped in ((1, True), (2, False)):
            cur = jnp.dot(s, w3[i], preferred_element_type=jnp.float32)
            y = cur.astype(s.dtype)
            if fam == "bn":
                y32 = y.astype(jnp.float32)
                y32 = (y32 - auxp[i, 0]) * jax.lax.rsqrt(auxp[i, 1] + 1e-5)
                y = (y32 * auxp[i, 2] + auxp[i, 3]).astype(s.dtype)
            elif roped:
                half = hd // 2
                t, b, l, qd = y.shape
                yh = y.reshape(t, b, l, heads, hd)
                x1 = yh[..., :half].astype(jnp.float32)
                x2 = yh[..., half:].astype(jnp.float32)
                c = auxp[0][None, None, :, None, :]
                sn = auxp[1][None, None, :, None, :]
                yh = jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn],
                                     -1).astype(y.dtype)
                y = yh.reshape(t, b, l, qd)
            out.append(lif_scan(y, SpikingConfig())[0])
        return tuple(out)
    return f(s, w3, auxp)


def layer_bench(fast: bool = False):
    """Layer-program step (``kernels/fused_layer`` via the engine's
    ``layer_step`` surface) on the three spikingformer-shaped whole-layer
    workloads: off (sequential oracle) vs fused vs pipeline x tile vs
    decoded. Counts-derived metrics are deterministic and CI-gated; wall
    clock is interpret-mode emulation, informative only. Each fused /
    pipeline row also cross-validates ``sim/balance_sim
    .binary_block_schedule`` — the numpy twin of the kernel's
    binary-phase occupancy map — against the measured ``counts[:, 3:5]``
    (``sim_binary_agreement`` = predicted / measured executed binary
    sub-blocks; sub-block-exact in practice). All three configs run even
    under ``--fast``: the counts are what CI gates, and the token config
    carries the layer-level hidden-fraction acceptance floor."""
    del fast
    import numpy as np

    from repro.core import dual_engine as de
    from repro.core.spiking import SpikingConfig
    from repro.kernels.fused_layer import fused_layer, reference_layer
    from repro.sim.balance_sim import binary_block_schedule

    scfg = SpikingConfig()
    delta = 0.3
    rows = []
    for name, fam, t, b, l, d, heads, hd, causal in FUSED_CONFIGS:
        ops, ff = _layer_workload(name, fam, t, b, l, d, heads, hd)
        args = ops + (delta,)
        kw_args = dict(family=fam, num_heads=heads, head_dim=hd,
                       scale=hd ** -0.5, causal=causal)
        seq_us = _time(jax.jit(lambda *a, k=kw_args: reference_layer(
            *a, scfg, **k)), *args)
        ksp, vsp = _proj_kv_spikes(ops[1], ops[2], ops[7], fam, heads, hd)
        pred = binary_block_schedule(np.asarray(ksp), np.asarray(vsp),
                                     heads, LAYER_L_BLOCK, delta,
                                     binarize=scfg.binarize_scores)
        pred_exec = int(pred.sum())
        for overlap, sparse in LAYER_MODES:
            if sparse == "decoded" and fam != "bn":
                continue
            base = {"bench": "layer", "config": name, "family": fam,
                    "shape": [t, b, l, d, heads, hd], "causal": causal,
                    "overlap": overlap, "sparse": sparse,
                    "sequential_us": round(seq_us, 1)}
            if overlap == "off":
                rows.append(dict(base, layer_us=round(seq_us, 1),
                                 wall_ratio=1.0, hidden_fraction=0.0,
                                 step_reduction=0.0))
                continue
            pipe = overlap == "pipeline"

            def call(*a, sp=sparse, pi=pipe, k=kw_args):
                return fused_layer(*a, sparse=sp, pipeline=pi,
                                   l_block=LAYER_L_BLOCK,
                                   c_block=LAYER_C_BLOCK, **k)[0]
            layer_us = _time(jax.jit(call), *args)
            _, counts = fused_layer(*args, sparse=sparse, pipeline=pipe,
                                    l_block=LAYER_L_BLOCK,
                                    c_block=LAYER_C_BLOCK, **kw_args)
            meas = np.asarray(counts)[:, 3:5, :]
            m = de.fused_step_metrics(
                counts, seq=l, k_dim=d, head_dim=hd, t_steps=t, batch=b,
                d_model=d, d_ff=ff, l_block=LAYER_L_BLOCK, sparse=sparse,
                c_block=LAYER_C_BLOCK, pipeline=pipe)
            rows.append(dict(
                base, layer_us=round(layer_us, 1),
                wall_ratio=round(seq_us / layer_us, 3),
                hidden_fraction=round(m["hidden_fraction"], 4),
                qkt_hidden_fraction=round(m["qkt_hidden_fraction"], 4),
                qktv_hidden_fraction=round(m["qktv_hidden_fraction"], 4),
                sparse_util=round(m["sparse_util"], 4),
                binary_util=round(m["binary_util"], 4),
                pipeline_iters=m["pipeline_iters"],
                executed_steps=m["executed_steps"],
                possible_steps=m["possible_steps"],
                step_reduction=round(m["step_reduction"], 4),
                sim_binary_agreement=round(
                    pred_exec / max(1, int(meas.sum())), 4),
                sim_binary_exact=bool(np.array_equal(pred, meas)),
                **{f"executed_{ph}": m[f"executed_{ph}"]
                   for ph in de.LAYER_PHASE_NAMES}))
    return rows


def bench(fast: bool = False):
    from repro.core import engine as E
    from repro.core.dual_engine import (measured_overlap_efficiency,
                                        measured_schedule)
    from repro.kernels.spike_matmul import block_occupancy

    shapes = SHAPES[:2] if fast else SHAPES
    rows = []
    for m, k, n in shapes:
        for block in BLOCKS:
            for sparsity in SPARSITIES:
                key = jax.random.PRNGKey(m + block + int(sparsity * 100))
                kw, ks = jax.random.split(key)
                s = coherent_spikes(ks, m, k, block, sparsity)
                w = jax.random.normal(kw, (k, n), jnp.float32)
                p = {"w": w}
                sparse_eng = E.EngineConfig(mode="sparse", block_m=block,
                                            block_n=block, block_k=block)
                dense_us = _time(jax.jit(
                    lambda s, p=p: E.spike_linear(p, s, engine=E.DENSE)), s)
                sparse_us = _time(jax.jit(
                    lambda s, p=p, e=sparse_eng: E.spike_linear(
                        p, s, engine=e)), s)
                occ = block_occupancy(s, min(block, m), min(block, k))
                skip = float(1.0 - occ.mean())
                tiles = occ.size  # MAC reduction is bounded by the grid
                rows.append({
                    "bench": "linear",
                    "shape": [m, k, n], "block": block,
                    "sparsity": sparsity,
                    "measured_sparsity": float(1.0 - s.mean()),
                    "dense_us": round(dense_us, 1),
                    "sparse_us": round(sparse_us, 1),
                    "wall_speedup": round(dense_us / sparse_us, 3),
                    "skip_fraction": round(skip, 4),
                    "modeled_speedup": round(
                        min(1.0 / max(1e-9, 1.0 - skip), float(tiles)), 3),
                })
    attn_rows = attention_bench(fast=fast)
    sp_rows = sparse_path_bench(fast=fast)
    fu_rows = fused_bench(fast=fast)
    la_rows = layer_bench(fast=fast)
    med = lambda xs: sorted(xs)[len(xs) // 2]
    sparse_med = med([r["sparse_us"] for r in rows])
    mxu_med = med([r["mxu_us"] for r in attn_rows])
    _, _, overlapped, serial = measured_schedule(sparse_med, mxu_med)
    derived = {
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        "points": len(rows),
        "max_modeled_speedup": max(r["modeled_speedup"] for r in rows),
        "mean_skip_at_0.9": round(sum(
            r["skip_fraction"] for r in rows if r["sparsity"] == 0.9) /
            max(1, sum(1 for r in rows if r["sparsity"] == 0.9)), 4),
        "attention_points": len(attn_rows),
        "mxu_vs_jnp_median": med([r["mxu_vs_jnp"] for r in attn_rows]),
        "popcount_vs_mxu_median": med(
            [r["popcount_vs_mxu"] for r in attn_rows]),
        # tile-vs-decoded on fine-grained/ragged patterns (DESIGN.md §9):
        # the tile skip is ~0 there by construction, and each executed
        # decoded chunk is a full-K dot, so decoded saves nothing either
        "sparse_path_points": len(sp_rows),
        "decoded_max_modeled_speedup": max(
            r["decoded_modeled_speedup"] for r in sp_rows),
        "tile_skip_on_ragged_max": max(
            r["tile_skip_fraction"] for r in sp_rows),
        "decoded_auto_wins": sum(
            1 for r in sp_rows if r["auto_choice"] == "decoded"),
        "sched_agreement_median": med(
            [r["sched_agreement"] for r in sp_rows]),
        # Fig. 5 overlap model on measured engine medians (us events)
        "measured_overlap": {
            "sparse_op_us": round(sparse_med, 1),
            "binary_op_us": round(mxu_med, 1),
            "overlapped_us": round(overlapped, 1),
            "serial_us": round(serial, 1),
            "hidden_fraction": round(
                measured_overlap_efficiency(sparse_med, mxu_med), 4),
        },
        # fused layer step: hidden fraction measured from the kernel's
        # own executed-step counts (per-row detail in fused_rows)
        "fused_overlap": {
            "points": len(fu_rows),
            "max_hidden_fraction": max(
                r["hidden_fraction"] for r in fu_rows),
            "best_config": max(fu_rows,
                               key=lambda r: r["hidden_fraction"])
            ["config"],
        },
        # layer-program step: the whole-layer occupancy map's measured
        # binary-hidden fraction (per-row detail in layer_rows); the
        # token config's fused/tile row carries the CI floor vs the
        # SSA-only bundle's hidden fraction
        "layer_overlap": {
            "points": len(la_rows),
            "token_hidden_fraction": next(
                r["hidden_fraction"] for r in la_rows
                if r["config"] == "spikingformer-lm"
                and r["overlap"] == "fused"),
            "min_hidden_fraction": min(
                r["hidden_fraction"] for r in la_rows
                if r["overlap"] != "off"),
            "sim_binary_exact_all": all(
                r["sim_binary_exact"] for r in la_rows
                if r["overlap"] != "off"),
        },
    }
    return rows + attn_rows + sp_rows + fu_rows + la_rows, derived


def to_blob(rows, derived):
    """Split the tagged row list into the artifact layout
    ({'rows': linear, 'attention_rows': attention, 'sparse_path_rows':
    tile-vs-decoded, 'fused_rows': fused SSA bundle, 'layer_rows':
    whole-layer program, 'derived': ...})."""
    return {"rows": [r for r in rows
                     if r.get("bench") not in ("attention", "sparse_path",
                                               "fused", "layer")],
            "attention_rows": [r for r in rows
                               if r.get("bench") == "attention"],
            "sparse_path_rows": [r for r in rows
                                 if r.get("bench") == "sparse_path"],
            "fused_rows": [r for r in rows if r.get("bench") == "fused"],
            "layer_rows": [r for r in rows if r.get("bench") == "layer"],
            "derived": derived}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default="artifacts/dual_engine_bench.json")
    args = ap.parse_args()
    rows, derived = bench(fast=args.fast)
    blob = to_blob(rows, derived)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(blob, f, indent=1)
    print("shape,block,sparsity,dense_us,sparse_us,wall_speedup,"
          "skip_fraction,modeled_speedup")
    for r in blob["rows"]:
        print(f"{'x'.join(map(str, r['shape']))},{r['block']},"
              f"{r['sparsity']},{r['dense_us']},{r['sparse_us']},"
              f"{r['wall_speedup']},{r['skip_fraction']},"
              f"{r['modeled_speedup']}")
    print("shape,causal,jnp_us,mxu_us,popcount_us,mxu_vs_jnp,"
          "popcount_vs_mxu")
    for r in blob["attention_rows"]:
        print(f"{'x'.join(map(str, r['shape']))},{r['causal']},"
              f"{r['jnp_us']},{r['mxu_us']},{r['popcount_us']},"
              f"{r['mxu_vs_jnp']},{r['popcount_vs_mxu']}")
    print("pattern,shape,tile_skip,decoded_mac_reduction,"
          "decoded_modeled_speedup,sched_agreement,auto")
    for r in blob["sparse_path_rows"]:
        print(f"{r['pattern']},{'x'.join(map(str, r['shape']))},"
              f"{r['tile_skip_fraction']},{r['decoded_mac_reduction']},"
              f"{r['decoded_modeled_speedup']},{r['sched_agreement']},"
              f"{r['auto_choice']}")
    print("config,shape,hidden_fraction,sparse_util,binary_util,"
          "step_reduction,proj_skip_fraction,fused_us,sequential_us")
    for r in blob["fused_rows"]:
        print(f"{r['config']},{'x'.join(map(str, r['shape']))},"
              f"{r['hidden_fraction']},{r['sparse_util']},"
              f"{r['binary_util']},{r['step_reduction']},"
              f"{r['proj_skip_fraction']},{r['fused_us']},"
              f"{r['sequential_us']}")
    print("config,overlap,sparse,hidden_fraction,step_reduction,"
          "sim_binary_agreement,layer_us,sequential_us")
    for r in blob["layer_rows"]:
        print(f"{r['config']},{r['overlap']},{r['sparse']},"
              f"{r['hidden_fraction']},{r['step_reduction']},"
              f"{r.get('sim_binary_agreement', '-')},{r['layer_us']},"
              f"{r['sequential_us']}")
    print(json.dumps(derived))


if __name__ == "__main__":
    main()
