"""Serving orchestrator: continuous batching with per-slot state, chunked
prefill, and an optional device mesh.

The paper's third pillar — the orchestrator that "dynamically manipulates
input dataflows" and load-balances heterogeneous work across parallel
units — mapped to the TPU serve path (DESIGN.md §Orchestrator):

  * per-slot state: every cache slot carries its own timeline (positions
    ``pos: (B,)``, validity tags ``(n_layers, B, s)``), so a finished
    sequence frees its slot and a queued request claims it mid-flight —
    the freed slot's tags are invalidated at admission, the new request
    decodes from position 0 and can never attend over the dead request's
    stale K/V;
  * chunked prefill: a prompt fills its slot's cache in ``chunk``-sized
    bites through the same decode step the generating slots ride (their
    rows are padding-masked via ``n_tok``), with the chunk width chosen
    per wave by the popcount-aware load-balance policy lifted from
    ``sim/decoder_sim.py``'s input-tracker model (:func:`choose_chunk`);
  * mesh-sharded decode: given a ``jax.sharding.Mesh``, slots shard over
    the 'data' axis and heads/vocab over 'model' using the existing
    ``parallel/sharding.py`` + ``parallel/rules.py`` tables — the same
    NamedSharding machinery launch/dryrun.py exercises at training scale;
  * greedy sampling (argmax) for determinism;
  * spiking LMs (``--arch spikingformer-lm``) decode against a
    *bit-packed* spike KV cache (uint32 words, AND-PopCount scoring —
    the paper's 32x spike-RAM compression); the server reports the
    measured cache footprint vs the unpacked layout;
  * quantized weights: ``--quantize int8|int4`` quantizes the param tree
    at load (repro.quant: symmetric per-output-channel scales, packed
    nibbles for int4) — the other half of the paper's dual-side
    compression. Every linear then serves integer codes (the decode
    path's analog matmuls dequantize through the epilogue scale; spike
    matmuls take the int8-accumulating kernel when the engine goes
    sparse) and the server reports the measured weight footprint next to
    the KV-cache report.
"""
from __future__ import annotations

import argparse
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, get_config
from repro.configs.base import RunShape
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.models import registry
from repro.parallel import rules as prules
from repro.parallel.sharding import (fit_spec_to_shape, rules_for_mesh,
                                     shard_put, use_rules)
from repro.sim import decoder_sim


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (L,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    # full logits row behind every sampled token (server trace_logits=True)
    logit_trace: List[np.ndarray] = field(default_factory=list)
    done: bool = False


def choose_chunk(remaining_prompt: int, n_decoding: int, max_chunk: int,
                 *, lanes: int = 4) -> int:
    """Prefill chunk width by the paper's Eq. 6 composite metric, driven
    by ``sim/decoder_sim.py``'s input-tracker model.

    Mapping: the prefill backlog of R tokens split into C-token bites is a
    stream of P_Ci = C-bit input words; the batched step is one worker
    whose decoder consumes a word in ``max(1, ceil(popcount / M))`` cycles
    (the input-tracker occupancy rule). The lane budget M is the per-wave
    useful-token throughput: ``lanes`` per prefilling slot, scaled by the
    decode riders — every generating slot contributes one useful token to
    each wave, so the marginal padding cost of a wider bite shrinks as
    the decode share grows. That is exactly Fig. 12's ``P_Ci_opt ~=
    G / (1 - sparsity)`` with sparsity = the decode share of the batch.
    F = 1 / (P_Ci * D^2) (Eq. 6, lambda folded out — it rescales, never
    reorders); argmax over power-of-two candidates.
    """
    if remaining_prompt <= 0 or max_chunk <= 1:
        return 1
    g_eff = lanes * (1 + n_decoding)
    best_c, best_f = 1, -1.0
    c = 1
    while c <= max_chunk:
        d = _drain_latency(remaining_prompt, c, g_eff)
        f = 1.0 / (c * float(d) * float(d))
        if f > best_f:
            best_c, best_f = c, f
        c *= 2
    return best_c


@functools.lru_cache(maxsize=65536)
def _drain_latency(remaining: int, chunk: int, g_eff: int) -> int:
    """Simulated drain latency of the bite stream (memoized: the policy
    runs on the serving hot loop's host side, and the backlog walks the
    same (remaining, chunk) grid wave after wave)."""
    n_full, rem = divmod(remaining, chunk)
    pc = np.full(n_full + (1 if rem else 0), chunk, np.int64)
    if rem:
        pc[-1] = rem
    dcfg = decoder_sim.DecoderConfig(p_ci=chunk, m_lanes=g_eff, p_wo=1)
    return decoder_sim.simulate_latency(pc, dcfg)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class BatchedServer:
    """Slot-based continuous batching over a fixed cache batch size.

    ``chunk``: prefill bite width; 0 = auto (:func:`choose_chunk` per
    wave). Wave widths are rounded up to powers of two so the jitted step
    compiles O(log max_chunk) distinct shapes, not one per width.
    ``mesh``: optional ``jax.sharding.Mesh`` with ('data', 'model') axes —
    params, cache, and the step's inputs/outputs get NamedShardings from
    the ``parallel/rules.py`` tables (slots on 'data', heads/vocab on
    'model').
    """

    def __init__(self, cfg, params, slots: int, max_len: int, *,
                 chunk: int = 0, mesh=None, trace_logits: bool = False):
        if not registry.supports_slots(cfg):
            raise ValueError(
                f"{cfg.name} ({cfg.family}) has no per-slot decode state; "
                f"continuous batching needs a slotted-decode family "
                f"({sorted(registry.SLOTTED_DECODE)})")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        # a chunk wider than the rolling window would overwrite its own
        # bite inside one scatter; cap at the window for banded caches
        cap = max_len if cfg.attn_type == "full" else min(max_len,
                                                          cfg.window)
        self.max_chunk = max(1, min(chunk if chunk > 0 else cap, cap))
        self.fixed_chunk = chunk > 0
        self.mesh = mesh
        self.trace_logits = trace_logits
        self.params = params
        # window rings get chunk-1 slots of headroom so a prefill bite's
        # write-before-attend scatter never evicts a live-window entry
        self.headroom = 0 if cfg.attn_type == "full" else self.max_chunk - 1
        self.cache = registry.init_cache(cfg, slots, max_len,
                                         chunk_headroom=self.headroom)
        self._build_step()
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.slot_pos = np.zeros(slots, np.int64)
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self.waves = 0

    # -- compiled steps ----------------------------------------------------

    def _build_step(self):
        cfg = self.cfg
        step = steps_lib.build_batched_serve_step(cfg)
        if self.mesh is None:
            self._rules = None
            self._step = jax.jit(step, donate_argnums=(1,))
            self._invalidate = jax.jit(
                lambda cache, mask: registry.invalidate_slots(cfg, cache,
                                                              mask),
                donate_argnums=(0,))
            return
        mesh = self.mesh
        self._rules = rules_for_mesh(mesh)
        shape = RunShape("serve", self.max_len, self.slots, "decode")
        pspecs = prules.params_partition(cfg, self.params, mesh)
        cache_abs = jax.eval_shape(
            lambda: registry.init_cache(cfg, self.slots, self.max_len,
                                        chunk_headroom=self.headroom))
        cspecs = prules.cache_partition(cfg, shape, mesh, cache_abs)
        pshard = prules.tree_shardings(pspecs, mesh)
        cshard = prules.tree_shardings(cspecs, mesh)
        dp = prules.dp_part(prules.batch_axes(shape, mesh))
        tok_spec = fit_spec_to_shape(P(dp, None), (self.slots, 1), mesh)
        vec_spec = fit_spec_to_shape(P(dp), (self.slots,), mesh)
        logits_spec = fit_spec_to_shape(
            P(dp, None, "model"), (self.slots, 1, cfg.vocab_size), mesh)
        rules = self._rules

        def step_with_rules(params, cache, tokens, pos, n_tok):
            with use_rules(rules):      # ambient only during tracing
                return step(params, cache, tokens, pos, n_tok)

        self._step = jax.jit(
            step_with_rules,
            in_shardings=(pshard, cshard, NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, vec_spec),
                          NamedSharding(mesh, vec_spec)),
            out_shardings=(NamedSharding(mesh, logits_spec), cshard),
            donate_argnums=(1,))
        self._invalidate = jax.jit(
            lambda cache, mask: registry.invalidate_slots(cfg, cache,
                                                          mask),
            in_shardings=(cshard, NamedSharding(mesh, P())),
            out_shardings=cshard, donate_argnums=(0,))
        self.params = shard_put(self.params, pspecs, mesh)
        self.cache = shard_put(self.cache, cspecs, mesh)

    # -- stats -------------------------------------------------------------

    def kv_cache_stats(self) -> Dict[str, float]:
        """Measured KV footprint; 'compression' is the ratio vs storing
        the same entries unpacked in the activation dtype (32x per word
        when the spiking packed-KV path is on, 1.0 otherwise). Leaves are
        selected by key (k/v payloads vs pos tags), not dtype sniffing."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        kv = [l for path, l in flat
              if getattr(path[-1], "key", None) in ("k", "v")]
        kv_bytes = sum(l.nbytes for l in kv)
        act_bytes = jnp.dtype(self.cfg.dtype).itemsize
        packed = any(l.dtype == jnp.uint32 for l in kv)
        if packed:
            words = -(-self.cfg.head_dim // 32)
            unpacked = kv_bytes // 4 // words * self.cfg.head_dim * act_bytes
        else:
            unpacked = kv_bytes
        return {"kv_bytes": kv_bytes, "packed": packed,
                "compression": unpacked / max(1, kv_bytes)}

    # -- scheduling --------------------------------------------------------

    def submit(self, req: Request):
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"exceeds cache capacity max_len={self.max_len}")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be "
                             f">= 1, got {req.max_new_tokens}")
        self.queue.append(req)

    def _admit(self):
        fresh = np.zeros(self.slots, bool)
        for s in range(self.slots):
            if self.slot_req[s] is None and self.queue:
                self.slot_req[s] = self.queue.pop(0)
                self.slot_pos[s] = 0
                fresh[s] = True
        if fresh.any():
            # the freed slots' validity tags go to -1: the new occupants
            # start at position 0 with an empty visible cache (this is the
            # slot-reuse bug fix — without it a re-admitted slot attends
            # over the previous request's stale K/V)
            self.cache = self._invalidate(self.cache, jnp.asarray(fresh))

    def step(self) -> bool:
        """One orchestrator wave: admit queued requests into free slots,
        issue a chunked-prefill bite or one decode token per active slot,
        run the batched step, sample, retire finished sequences."""
        self._admit()
        active = [s for s in range(self.slots) if self.slot_req[s]]
        if not active:
            return False
        backlog = sum(max(0, len(self.slot_req[s].prompt)
                          - self.slot_pos[s]) for s in active)
        n_decoding = sum(self.slot_pos[s] >= len(self.slot_req[s].prompt)
                         for s in active)
        chunk = self.max_chunk if self.fixed_chunk else \
            choose_chunk(backlog, n_decoding, self.max_chunk)
        n_tok = np.zeros(self.slots, np.int32)
        for s in active:
            req, p = self.slot_req[s], int(self.slot_pos[s])
            if p < len(req.prompt):
                n_tok[s] = min(chunk, len(req.prompt) - p,
                               self.max_len - p)
            else:
                n_tok[s] = 1
        width = _next_pow2(int(n_tok.max()))
        tokens = np.zeros((self.slots, width), np.int32)
        for s in active:
            req, p, n = self.slot_req[s], int(self.slot_pos[s]), int(n_tok[s])
            if p < len(req.prompt):
                tokens[s, :n] = req.prompt[p:p + n]
            else:
                # the wave that finishes a prompt always samples the first
                # generated token, so a decoding slot is never empty here
                tokens[s, 0] = req.generated[-1]
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.slot_pos, jnp.int32), jnp.asarray(n_tok))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))    # (slots, width)
        for s in active:
            req, n = self.slot_req[s], int(n_tok[s])
            self.slot_pos[s] += n
            p = int(self.slot_pos[s])
            if p >= len(req.prompt):
                req.generated.append(int(nxt[s, n - 1]))
                if self.trace_logits:
                    req.logit_trace.append(np.asarray(logits[s, n - 1]))
            # retire when generation quota is met or the cache is full:
            # position max_len - 1 is the last usable entry, and the token
            # sampled from it is still kept (it just can't be fed back)
            if len(req.generated) >= req.max_new_tokens or \
                    p >= self.max_len:
                req.done = True
                self.completed.append(req)
                self.slot_req[s] = None
        self.waves += 1
        return True

    def run(self) -> int:
        """Drain the queue; returns the total wave count (self.waves)."""
        while self.step():
            pass
        return self.waves


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b",
                    choices=list(ALL_ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk width; 0 = popcount-aware policy")
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL serving mesh, e.g. 2x2 (needs that "
                         "many devices; '' = unsharded)")
    ap.add_argument("--quantize", default="none",
                    choices=["none", "int8", "int4"],
                    help="quantize linear weights at load (repro.quant); "
                         "reports the measured footprint compression")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if not registry.has_decode(cfg):
        raise SystemExit(f"{args.arch} has no decode step")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_serve_mesh(d, m)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    wrep = None
    if args.quantize != "none":
        from repro.core.engine import EngineConfig
        from repro.quant import footprint_report, quantize_tree
        qparams = quantize_tree(params, args.quantize)
        wrep = footprint_report(params, qparams)
        # declare the weight datapath on the engine (the per-call dispatch
        # keys off the quantized param dicts; this records intent and lets
        # 'auto' matmul routing stay in effect for the spike call sites)
        eng = cfg.engine if cfg.engine is not None else EngineConfig()
        cfg = cfg.replace(engine=eng.replace(weights=args.quantize))
        params = qparams
    server = BatchedServer(cfg, params, args.slots, args.max_len,
                           chunk=args.chunk, mesh=mesh)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        server.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                       args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new))
    kv = server.kv_cache_stats()
    print(f"[serve] kv cache {kv['kv_bytes']/1024:.1f} KiB "
          f"(packed={kv['packed']}, {kv['compression']:.0f}x vs unpacked)"
          + (f", mesh={args.mesh}" if mesh is not None else ""))
    if wrep is not None:
        print(f"[serve] weights {wrep['quant_weight_bytes']/1024:.1f} KiB "
              f"({args.quantize}): {wrep['compression']:.2f}x vs "
              f"{jnp.dtype(cfg.dtype).name} linears "
              f"({wrep['total_compression']:.2f}x whole tree)")
    t0 = time.time()
    steps = server.run()
    dt = time.time() - t0
    n_gen = sum(len(r.generated) for r in server.completed)
    n_pre = sum(len(r.prompt) for r in server.completed)
    dev = jax.devices()[0]
    print(f"[serve] {len(server.completed)} requests, {n_gen} generated "
          f"(+{n_pre} prompt) tokens, {steps} waves in {dt:.2f}s "
          f"({(n_gen + n_pre)/dt:.1f} tok/s, compiles included, on "
          f"{dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"{cfg.name} {'smoke' if args.smoke else 'full'} config)")
    for r in server.completed[:3]:
        print(f"  req {r.rid}: {r.generated}")


if __name__ == "__main__":
    main()
