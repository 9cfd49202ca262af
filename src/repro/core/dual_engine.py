"""Dual-engine latency-hiding pipeline schedule (paper Section III-C,
Eq. 3/4) — analytic model *and* measurement consumer.

FireFly-T overlaps the sparse engine (Q/K/V projections) with the binary
engine (QK^T, QK^T V) across attention heads. This module holds the
discrete-event model of that schedule (Fig. 5) and the consumer that
turns the fused layer kernel's (``kernels/fused_layer.py``) *measured*
per-phase executed-step counts into a hidden-fraction / utilization
report (:func:`fused_step_metrics`). It is used by:

* ``benchmarks/paper_figures.py``        — the Fig. 5 spatial-temporal
  overlap diagram (``pipeline_schedule``),
* ``benchmarks/dual_engine_bench.py``    — the measured-overlap rows
  (``measured_schedule`` on wall-clock medians; ``fused_step_metrics``
  on the fused layer kernel's step counts),
* ``examples/dual_engine_walkthrough.py``— the Eq. 4 engine-sizing rule
  (``required_binary_parallelism``) used to pick ``P_B*`` for a network.

On TPU the same overlap re-appears as HBM-prefetch ∥ MXU pipelining inside
the fused attention kernel and as compute/collective overlap at the
distribution layer (see DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple, Union


@dataclasses.dataclass(frozen=True)
class EngineParallelism:
    """Hardware parallelism knobs (Table II)."""
    P_Ts: int = 2
    P_Fx: int = 4
    P_Ci: int = 16
    P_Co: int = 64
    # binary engine systolic array + inner-product width
    P_Bm: int = 4
    P_Bn: int = 4
    P_Bk: int = 32

    @property
    def P_s(self) -> int:
        return self.P_Ts * self.P_Fx * self.P_Ci * self.P_Co

    @property
    def P_b(self) -> int:
        return self.P_Bm * self.P_Bn * self.P_Bk


@dataclasses.dataclass(frozen=True)
class AttentionWorkload:
    """Per-head attention workload (Eq. 3)."""
    T_s: int
    F_h: int
    F_w: int
    C_i: int          # embedding dim d
    P_Co: int         # output-channel tile == per-head dim in the schedule
    heads: int = 8

    @property
    def L(self) -> int:
        return self.F_h * self.F_w

    def W_s(self) -> int:
        """Sparse-engine work per head per projection (MACs)."""
        return self.T_s * self.L * self.C_i * self.P_Co

    def W_b(self) -> int:
        """Binary-engine work per head per attention matmul (MACs)."""
        return self.T_s * self.L * self.L * self.P_Co


def required_binary_parallelism(w: AttentionWorkload, p: EngineParallelism) -> float:
    """Eq. 4: P_b ~= 2/3 * (Fh*Fw / Ci) * P_s for balanced overlap."""
    return 2.0 / 3.0 * (w.L / w.C_i) * p.P_s


# Per-head timing inputs: a scalar (every op identical — the original
# two-scalar model), or a per-head sequence whose entries are scalars or
# (Q, K, V) triples (sparse) / (QK^T, QK^TV) pairs (binary).
PerHead = Union[float, Sequence]


def _sparse_triples(ts: PerHead, heads: int) -> List[Tuple[float, ...]]:
    if not isinstance(ts, Sequence):
        return [(float(ts),) * 3] * heads
    if len(ts) != heads:
        raise ValueError(f"per-head sparse timings: got {len(ts)} entries "
                         f"for {heads} heads")
    return [(float(e),) * 3 if not isinstance(e, Sequence)
            else tuple(float(x) for x in e) for e in ts]


def _binary_pairs(tb: PerHead, heads: int) -> List[Tuple[float, ...]]:
    if not isinstance(tb, Sequence):
        return [(float(tb),) * 2] * heads
    if len(tb) != heads:
        raise ValueError(f"per-head binary timings: got {len(tb)} entries "
                         f"for {heads} heads")
    return [(float(e),) * 2 if not isinstance(e, Sequence)
            else tuple(float(x) for x in e) for e in tb]


def _event_schedule(ts: PerHead, tb: PerHead, heads: int
                    ) -> Tuple[List[tuple], List[tuple], float, float]:
    """Core event loop shared by the analytic and measured schedules:
    the sparse engine serially computes Q_h, K_h, V_h per head (``ts``
    each); the binary engine computes ``QK^T_h`` once Q_h,K_h are done
    and ``QK^T V_h`` once V_h is done (``tb`` each). ``ts``/``tb`` are
    scalars or per-head sequences (see :data:`PerHead`); the scalar path
    is numerically pinned to the original two-scalar model."""
    trips = _sparse_triples(ts, heads)
    pairs = _binary_pairs(tb, heads)
    sparse_events, binary_events = [], []
    t_sparse = 0.0
    qk_done = {}
    v_done = {}
    for h in range(heads):
        for name, dt in zip(("Q", "K", "V"), trips[h]):
            sparse_events.append((f"{name}{h}", t_sparse, t_sparse + dt))
            t_sparse += dt
            if name == "K":
                qk_done[h] = t_sparse
            if name == "V":
                v_done[h] = t_sparse
    t_bin = 0.0
    for h in range(heads):
        t_qk, t_qkv = pairs[h]
        start = max(t_bin, qk_done[h])
        binary_events.append((f"QK^T {h}", start, start + t_qk))
        t_bin = start + t_qk
        start = max(t_bin, v_done[h])
        binary_events.append((f"QK^TV {h}", start, start + t_qkv))
        t_bin = start + t_qkv

    total_overlapped = max(t_sparse, t_bin if binary_events else 0.0)
    if not isinstance(tb, Sequence):
        # the original scalar expression, verbatim (float-op-for-float-op:
        # the scalar path is pinned numerically unchanged)
        total_serial = t_sparse + 2 * float(tb) * heads
    else:
        total_serial = t_sparse + sum(t_qk + t_qkv
                                      for t_qk, t_qkv in pairs)
    return sparse_events, binary_events, total_overlapped, total_serial


def pipeline_schedule(w: AttentionWorkload, p: EngineParallelism,
                      sparsity: float = 0.0
                      ) -> Tuple[List[tuple], List[tuple], int, int]:
    """Discrete-event schedule of the latency-hiding pipeline (Fig. 5).

    Op latencies come from the analytic MAC model (Eq. 3 work over
    Table II parallelism; sparse throughput scales with input density
    when skipping is on). Returns (sparse_events, binary_events,
    total_overlapped, total_serial); events are (name, start, end) in
    cycles.
    """
    ts = w.W_s() / (p.P_s / max(1e-9, 1.0 - sparsity))  # sparse op latency
    tb = w.W_b() / p.P_b                                # binary op latency
    se, be, overlapped, serial = _event_schedule(ts, tb, w.heads)
    return se, be, math.ceil(overlapped), math.ceil(serial)


def measured_schedule(sparse_op_us: PerHead, binary_op_us: PerHead,
                      heads: int = 8
                      ) -> Tuple[List[tuple], List[tuple], float, float]:
    """Fig. 5 schedule fed with *measured* engine timings instead of the
    analytic MAC model — e.g. the per-call medians
    ``benchmarks/dual_engine_bench.py`` writes to
    ``artifacts/dual_engine_bench.json`` (``sparse_us`` from the matmul
    sweep, ``mxu_us`` from the attention sweep). Each input is a scalar
    (all heads/ops identical) or a per-head sequence — entries scalars or
    (Q, K, V) triples / (QK^T, QK^TV) pairs, e.g. derived from the fused
    kernel's per-phase executed-step counts. Events are in the same unit
    as the inputs; returns (sparse_events, binary_events,
    total_overlapped, total_serial).
    """
    if not isinstance(sparse_op_us, Sequence):
        sparse_op_us = float(sparse_op_us)
    if not isinstance(binary_op_us, Sequence):
        binary_op_us = float(binary_op_us)
    return _event_schedule(sparse_op_us, binary_op_us, heads)


def measured_overlap_efficiency(sparse_op_us: PerHead,
                                binary_op_us: PerHead,
                                heads: int = 8) -> float:
    """Fraction of the serial dual-engine latency the overlap hides,
    from measured timings: 1 - overlapped/serial."""
    _, _, overlapped, serial = measured_schedule(sparse_op_us,
                                                 binary_op_us, heads)
    if serial <= 0:
        return 0.0
    return 1.0 - overlapped / serial


def schedule_metrics(sparse_op_us: PerHead, binary_op_us: PerHead,
                     heads: int = 8) -> Dict[str, float]:
    """Hidden fraction *and* per-engine utilization of the Fig. 5
    schedule: utilization is each engine's busy time over the overlapped
    makespan (1.0 = that engine never stalls; the paper sizes ``P_B*`` so
    both stay near 1 — Eq. 4)."""
    se, be, overlapped, serial = measured_schedule(sparse_op_us,
                                                   binary_op_us, heads)
    sparse_busy = sum(e - s for _, s, e in se)
    binary_busy = sum(e - s for _, s, e in be)
    return {
        "overlapped": overlapped,
        "serial": serial,
        "hidden_fraction": 0.0 if serial <= 0 else 1.0 - overlapped / serial,
        "sparse_util": 0.0 if overlapped <= 0 else sparse_busy / overlapped,
        "binary_util": 0.0 if overlapped <= 0 else binary_busy / overlapped,
    }


LAYER_PHASE_NAMES = ("q", "k", "v", "qkt", "qktv", "wo", "up", "down")


def _interval_overlap(binary_events: List[tuple],
                      sparse_events: List[tuple]) -> float:
    """Total binary busy time that lies under sparse busy time."""
    total = 0.0
    for _, b0, b1 in binary_events:
        for _, s0, s1 in sparse_events:
            lo, hi = max(b0, s0), min(b1, s1)
            if hi > lo:
                total += hi - lo
    return total


def layer_event_schedule(macs: Dict[str, List[float]], heads: int
                         ) -> Tuple[List[tuple], List[tuple]]:
    """Discrete-event schedule of the *layer program* (the fused-layer
    grid of ``kernels/fused_layer.py``): the sparse engine walks the
    phases in the kernel's phase-major grid order (q, k, v over all
    heads, then wo, up, down), the binary engine runs qkt/qktv as their
    operands land, and ``wo`` of head h stalls on ``qktv`` of head h
    (the context dependency). ``macs[phase][h]`` is the executed-MAC
    duration of that (phase, head) work item.

    Returns (sparse_events, binary_events) as (name, start, end) lists.
    """
    se: List[tuple] = []
    be: List[tuple] = []
    t_s = 0.0
    t_b = 0.0
    k_done: Dict[int, float] = {}
    v_done: Dict[int, float] = {}
    ctx_done: Dict[int, float] = {}
    for ph in ("q", "k", "v"):
        for h in range(heads):
            dt = macs[ph][h]
            se.append((f"{ph}{h}", t_s, t_s + dt))
            t_s += dt
            if ph == "k":
                k_done[h] = t_s
            elif ph == "v":
                v_done[h] = t_s
    for h in range(heads):
        dt = macs["qkt"][h]
        start = max(t_b, k_done[h])
        be.append((f"qkt{h}", start, start + dt))
        t_b = start + dt
    for h in range(heads):
        dt = macs["qktv"][h]
        start = max(t_b, v_done[h])
        be.append((f"qktv{h}", start, start + dt))
        t_b = start + dt
        ctx_done[h] = t_b
    for h in range(heads):
        dt = macs["wo"][h]
        start = max(t_s, ctx_done[h])
        se.append((f"wo{h}", start, start + dt))
        t_s = start + dt
    for ph in ("up", "down"):
        for h in range(heads):
            dt = macs[ph][h]
            se.append((f"{ph}{h}", t_s, t_s + dt))
            t_s += dt
    return se, be


def fused_step_metrics(counts, *, seq: int, k_dim: int, head_dim: int,
                       t_steps: int, batch: int, d_model: int, d_ff: int,
                       l_block: int, sparse: str = "tile",
                       c_block: int = None) -> Dict[str, float]:
    """Measured overlap report from the fused layer kernel's
    ``(H, 8, n_l_blocks)`` occupancy map (``kernels/fused_layer.
    fused_layer``: executed sub-blocks per head, phase and L-block;
    ``sparse='decoded'`` needs ``c_block``) -> executed-MAC phase
    durations -> layer event schedule -> *binary-hidden fraction* (the
    share of binary-engine busy time that runs under sparse-engine busy
    time). The MLP tail (wo/up/down) gives the sparse engine work to run
    *under* the binary tail.

    This is the "measured, not modeled" hidden fraction: op durations in
    the Fig. 5 schedule are the *executed* MACs of each phase — a
    sub-block the kernel skipped simply isn't there. Deterministic for a
    fixed input, so CI gates it (benchmarks/check_regression.py).
    """
    cnt = [[[int(c) for c in lbrow] for lbrow in row] for row in counts]
    heads = len(cnt)
    nlb = len(cnt[0][0])
    rows = [min(l_block, seq - lb * l_block) for lb in range(nlb)]
    ffc = d_ff // heads
    decoded = sparse == "decoded"
    proj_k = c_block if decoded else k_dim
    unit = {"q": proj_k * head_dim, "k": proj_k * head_dim,
            "v": proj_k * head_dim,
            "qkt": seq * head_dim, "qktv": seq * head_dim,
            "wo": head_dim * d_model, "up": d_model * ffc,
            "down": ffc * d_model}
    macs = {ph: [float(sum(cnt[h][p][lb] * rows[lb]
                           for lb in range(nlb)) * unit[ph])
                 for h in range(heads)]
            for p, ph in enumerate(LAYER_PHASE_NAMES)}
    se, be = layer_event_schedule(macs, heads)
    sparse_busy = sum(e - s for _, s, e in se)
    binary_busy = sum(e - s for _, s, e in be)
    makespan = max([e for _, _, e in se + be], default=0.0)
    hidden = _interval_overlap(be, se)
    qkt_ev = [ev for ev in be if ev[0].startswith("qkt") and
              not ev[0].startswith("qktv")]
    qktv_ev = [ev for ev in be if ev[0].startswith("qktv")]
    qkt_busy = sum(e - s for _, s, e in qkt_ev)
    qktv_busy = sum(e - s for _, s, e in qktv_ev)
    executed = {ph: sum(cnt[h][p][lb] for h in range(heads)
                        for lb in range(nlb))
                for p, ph in enumerate(LAYER_PHASE_NAMES)}
    per_block = t_steps * batch * heads * nlb
    possible = {ph: per_block for ph in LAYER_PHASE_NAMES}
    if decoded:
        nc = -(-k_dim // c_block)
        for ph in ("q", "k", "v"):
            possible[ph] = per_block * nc
    tot_exec = sum(executed.values())
    tot_poss = sum(possible.values())
    return {
        "heads": heads,
        "phases": len(LAYER_PHASE_NAMES),
        "l_blocks": nlb,
        "executed_steps": tot_exec,
        "possible_steps": tot_poss,
        "step_reduction": 0.0 if tot_poss == 0
        else 1.0 - tot_exec / tot_poss,
        "sparse_busy": sparse_busy,
        "binary_busy": binary_busy,
        "makespan": makespan,
        "sparse_util": 0.0 if makespan <= 0 else sparse_busy / makespan,
        "binary_util": 0.0 if makespan <= 0 else binary_busy / makespan,
        # the binary-hidden fraction: binary busy time overlapped by
        # sparse busy time, over binary busy time
        "hidden_fraction": 0.0 if binary_busy <= 0
        else hidden / binary_busy,
        "qkt_hidden_fraction": 0.0 if qkt_busy <= 0
        else _interval_overlap(qkt_ev, se) / qkt_busy,
        "qktv_hidden_fraction": 0.0 if qktv_busy <= 0
        else _interval_overlap(qktv_ev, se) / qktv_busy,
        **{f"executed_{ph}": executed[ph] for ph in LAYER_PHASE_NAMES},
    }


def pipeline_efficiency(w: AttentionWorkload, p: EngineParallelism,
                        sparsity: float = 0.0) -> float:
    """Fraction of attention latency hidden: 1 -> perfect (O(3TsLd^2))."""
    _, _, overlapped, serial = pipeline_schedule(w, p, sparsity)
    ideal = 3 * w.heads * (w.W_s() / (p.P_s / max(1e-9, 1.0 - sparsity)))
    if overlapped <= 0:
        return 1.0
    return min(1.0, ideal / overlapped)


def complexity_reduction(w: AttentionWorkload) -> Tuple[int, int]:
    """(serial, overlapped) op counts: O(3TsLd^2 + 2TsL^2 d) -> O(3TsLd^2).

    Uses d == heads * P_Co as the full embedding dim.
    """
    d = w.C_i
    serial = 3 * w.T_s * w.L * d * d + 2 * w.T_s * w.L * w.L * d
    overlapped = 3 * w.T_s * w.L * d * d
    return serial, overlapped
