"""Dense decoder-only transformer family.

Covers: nemotron-4-15b (full attn, squared-ReLU), gemma3-12b (5:1
local:global, qk-norm), h2o-danube-3-4b (SWA), granite-20b (MQA),
llava-next-mistral-7b backbone (SWA; see vlm.py for the frontend).

Execution modes:
  forward      — full-sequence (train / prefill); lax.scan over layers,
                 chunked flash attention (banded for SWA / local layers).
  decode_step  — one token against a KV cache (full or rolling window).
  spiking mode — activations are LIF spike trains over T_s time steps and
                 attention is binary attention (the paper's SSA); enabled by
                 cfg.spiking (DESIGN.md §5).
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.bitpack import pack_bits, unpack_bits
from repro.core.scopes import annotate
from repro.core.spiking import binarize, lif_scan
from repro.parallel.sharding import constrain
from . import nn

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p = {
        "ln1": nn.rmsnorm_init(cfg.d_model, dt),
        "wq": nn.linear_init(ks[0], cfg.d_model, cfg.q_dim, dtype=dt),
        "wk": nn.linear_init(ks[1], cfg.d_model, cfg.kv_dim, dtype=dt),
        "wv": nn.linear_init(ks[2], cfg.d_model, cfg.kv_dim, dtype=dt),
        "wo": nn.linear_init(ks[3], cfg.q_dim, cfg.d_model,
                             std=1.0 / math.sqrt(cfg.q_dim * 2 * cfg.num_layers),
                             dtype=dt),
        "ln2": nn.rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.moe is None:
        p["mlp"] = nn.mlp_init(ks[4], cfg.d_model, cfg.d_ff, gated=cfg.gated,
                               dtype=dt)
    else:
        from repro.models import moe
        p["moe"] = moe.ffn_init(ks[4], cfg)
    if cfg.qk_norm:
        p["q_norm"] = nn.rmsnorm_init(cfg.head_dim, dt)
        p["k_norm"] = nn.rmsnorm_init(cfg.head_dim, dt)
    if cfg.spiking is not None:
        p["delta"] = jnp.asarray(cfg.spiking.attn_threshold_init, jnp.float32)
    return p


def init(cfg: ModelConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": nn.embedding_init(k_embed, cfg.vocab_size, cfg.d_model, dt),
        "final_norm": nn.rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.attn_type == "local_global":
        g = cfg.num_layers // cfg.global_every
        keys = jax.random.split(k_layers, cfg.num_layers).reshape(
            g, cfg.global_every, 2)
        params["groups"] = jax.vmap(jax.vmap(lambda k: _layer_init(k, cfg)))(keys)
    else:
        keys = jax.random.split(k_layers, cfg.num_layers)
        params["layers"] = jax.vmap(lambda k: _layer_init(k, cfg))(keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = nn.linear_init(k_head, cfg.d_model,
                                           cfg.vocab_size, dtype=dt)
    return params


# ---------------------------------------------------------------------------
# layer application (full sequence)
# ---------------------------------------------------------------------------


def _project_qkv(p, cfg: ModelConfig, h, positions, repeat_kv: bool = False,
                 kind: str = "full"):
    """h: (..., S, D) -> q (..., S, H, hd), k/v (..., S, KH, hd), roped
    (``cfg.yarn`` on layers of ``kind`` 'full').

    ``repeat_kv`` broadcasts KV heads up to H *before* attention (full-seq
    paths): with heads TP-sharded over 'model', the grouped-GQA reshape
    (H -> KH x rep) would cross shard boundaries and force all-gathers —
    repeating locally keeps every reshape sharding-aligned (each shard
    expands only its own KV slice). Decode paths keep KV unrepeated (the
    cache stores KH heads).
    """
    lead = h.shape[:-2]
    s = h.shape[-2]
    q = nn.linear(p["wq"], h).reshape(*lead, s, cfg.num_heads, cfg.head_dim)
    k = nn.linear(p["wk"], h).reshape(*lead, s, cfg.num_kv_heads, cfg.head_dim)
    v = nn.linear(p["wv"], h).reshape(*lead, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = nn.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    # rope operates on (B, L, H, D): fold extra leading dims
    yarn = cfg.yarn if kind == "full" else None
    q = nn.rope(q.reshape(-1, s, cfg.num_heads, cfg.head_dim), positions,
                cfg.rope_theta, yarn).reshape(*lead, s, cfg.num_heads,
                                              cfg.head_dim)
    k = nn.rope(k.reshape(-1, s, cfg.num_kv_heads, cfg.head_dim), positions,
                cfg.rope_theta, yarn).reshape(*lead, s, cfg.num_kv_heads,
                                              cfg.head_dim)
    if repeat_kv and cfg.num_heads != cfg.num_kv_heads:
        rep = cfg.num_heads // cfg.num_kv_heads
        k = jnp.repeat(k, rep, axis=-2)
        v = jnp.repeat(v, rep, axis=-2)
    prefix = (None,) * (len(lead) - 1) + ("batch", "seq")
    kv_name = "heads" if repeat_kv else "kv_heads"
    q = constrain(q, *prefix, "heads", None)
    k = constrain(k, *prefix, kv_name, None)
    v = constrain(v, *prefix, kv_name, None)
    return q, k, v


def _attend_full_seq(cfg: ModelConfig, kind: str, q, k, v, delta=None):
    """kind: 'full' | 'window'. Shapes (B', S, H/KH, hd)."""
    window = cfg.window if kind == "window" else None
    if cfg.spiking is not None:
        if window is None:
            # binary-engine dispatch (jnp / MXU kernel / popcount) via the
            # ambient engine; (B', S, H, hd) -> (B', H, S, hd) puts (S, hd)
            # in the primitive's trailing position. KV heads are already
            # repeated to H here (repeat_kv=True in _project_qkv).
            from repro.core.attention import spiking_attention
            swap = lambda u: u.transpose(0, 2, 1, 3)
            ctx = spiking_attention(swap(q), swap(k), swap(v), cfg.spiking,
                                    delta_score=delta, causal=True)
            return swap(ctx)
        # sliding-window spiking SSA keeps the banded jnp dataflow (the
        # fused kernel's block skip is causal-only for now)
        return nn.binary_flash_attention(
            q, k, v, delta=delta, alpha=cfg.spiking.surrogate_alpha,
            causal=True, window=window,
            binarize_scores=cfg.spiking.binarize_scores)
    if window is not None:
        return nn.banded_flash_attention(q, k, v, window=window)
    return nn.flash_attention(q, k, v, causal=True)


def _attn_scope(cfg: ModelConfig, kind: str):
    """``attn.window`` / ``attn.full`` around a layer's attention half;
    none in spiking mode, whose phases carry the engine's scopes."""
    if cfg.spiking is not None:
        return contextlib.nullcontext()
    return annotate(f"attn.{kind}")


def _ffn(p, cfg: ModelConfig, h, train: bool = False, valid=None):
    """The analog FFN: the MLP, or with ``cfg.moe`` the expert layer
    (rows where ``valid`` is False join no expert)."""
    if cfg.moe is None:
        return nn.mlp(p["mlp"], h, cfg.act)
    from repro.models import moe
    return moe.moe_ffn(p["moe"], h, cfg, train=train, valid=valid)[0]


def _spike(x, cfg: ModelConfig, t_steps: int):
    """LIF over the time axis; x: (T, B, S, D) currents -> spikes."""
    spikes, _ = lif_scan(x, cfg.spiking)
    return spikes


def apply_layer(p, cfg: ModelConfig, x, positions, kind: str, train: bool):
    """x: (B, S, D) or (T, B, S, D) in spiking mode."""
    spiking = cfg.spiking is not None
    if spiking and kind == "full":
        # the whole layer program — ln1 + SSA bundle + wo + residual +
        # ln2 + spiking MLP + residual — is engine-owned: with
        # overlap='fused' | 'pipeline' both overlay halves run as one
        # Pallas grid spanning the layer (Fig. 5, the MLP phases riding
        # the same wavefront; pipeline adds the timestep axis to the
        # grid), otherwise the engine composes the sequential reference
        # (which still hands the bundle to ssa_step_causal). The
        # sliding-window branch below keeps its banded jnp dataflow
        # (the fused grid is full-attention only).
        from repro.core.engine import layer_step_causal
        return layer_step_causal(p, cfg, x, positions, train=train)
    with _attn_scope(cfg, kind):
        h = nn.rmsnorm(p["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(p, cfg, h, positions, repeat_kv=True,
                               kind=kind)
        if spiking:
            t = x.shape[0]
            q, k, v = (_spike(u, cfg, t) for u in (q, k, v))
            fold = lambda u: u.reshape(-1, *u.shape[2:])
            attn = _attend_full_seq(cfg, kind, fold(q), fold(k), fold(v),
                                    delta=p["delta"])
        else:
            attn = _attend_full_seq(cfg, kind, q, k, v)
        attn = attn.reshape(*x.shape[:-1], cfg.q_dim)
        # q_dim stays 'model'-sharded into the row-parallel wo (§Perf F2 —
        # constraining to replicated here forced a (B,S,H,hd) all-gather)
        attn = constrain(attn, "batch", "seq", "model")
        x = x + nn.linear(p["wo"], attn)
    h2 = nn.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if spiking:
        up = nn.linear(p["mlp"]["up"], h2)
        hidden = _spike(up, cfg, x.shape[0])
        x = x + nn.linear(p["mlp"]["down"], hidden)
    else:
        x = x + _ffn(p, cfg, h2, train=train)
    return constrain(x, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, batch, *, train: bool = False,
            inputs_embeds: Optional[jax.Array] = None):
    """batch: {'tokens': (B, S)}; returns (logits (B, S, V), aux dict)."""
    tokens = batch["tokens"]
    with annotate("transformer.embed"):
        x = nn.embed(params["embed"], tokens) if inputs_embeds is None \
            else inputs_embeds
        x = constrain(x, "batch", "seq", "embed")
        s = x.shape[-2]
        positions = jnp.arange(s)
        if cfg.spiking is not None:
            x = jnp.broadcast_to(x[None],
                                 (cfg.spiking.time_steps,) + x.shape)

    layer_fn = apply_layer
    if cfg.remat and train:
        layer_fn = jax.checkpoint(apply_layer,
                                  static_argnums=(1, 4, 5),
                                  policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.attn_type == "local_global":
        def body(x, gp):
            for j in range(cfg.global_every):
                sub = jax.tree_util.tree_map(lambda a: a[j], gp)
                kind = "full" if j == cfg.global_every - 1 else "window"
                x = layer_fn(sub, cfg, x, positions, kind, train)
            return x, None
        stacked = params["groups"]
    else:
        kind = "window" if cfg.attn_type == "swa" else "full"

        def body(x, lp):
            return layer_fn(lp, cfg, x, positions, kind, train), None
        stacked = params["layers"]
    with annotate("transformer.layers"):
        x, _ = jax.lax.scan(body, x, stacked)

    with annotate("transformer.head"):
        if cfg.spiking is not None:
            x = x.mean(axis=0)  # rate decoding over T_s
        x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = nn.unembed(params["embed"], x)
        else:
            logits = nn.linear(params["lm_head"], x).astype(jnp.float32)
        logits = constrain(logits, "batch", "seq", "vocab")
    return logits, {}


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------


def _cache_len(cfg: ModelConfig, kind: str, max_len: int,
               headroom: int = 0) -> int:
    """Ring length for a cache of this kind. ``headroom`` (chunked
    prefill) widens window rings by up to chunk-1 extra slots: a C-token
    bite is scattered *before* attention runs, and with a bare
    ``window``-long ring its later writes would evict entries still
    inside earlier in-bite queries' windows (write at pos p+i lands on
    the slot holding p+i-s_len, which query p+j needs iff
    p+i-s_len > p+j-window — impossible once s_len >= window + C - 1)."""
    if kind != "window":
        return max_len
    return min(cfg.window + headroom, max_len)


def _packed_kv(cfg: ModelConfig) -> bool:
    """Spiking decode caches store K/V bit-packed (uint32 words) when the
    config's engine asks for it — the paper's 32x spike-RAM compression
    (byte-level SRAM dataflow) carried to the serve path. Cache layout is
    static per config, so this reads ``cfg.engine`` directly rather than
    the ambient engine."""
    return (cfg.spiking is not None and cfg.engine is not None
            and cfg.engine.packed_kv)


def kv_heads_major(cfg: ModelConfig) -> bool:
    """Analog K/V caches are stored heads-major, (..., B, KH, S, hd): each
    head's positions lie together, as the attention products read them,
    and the positions (not the few KV heads) are the tiled second-minor
    dim. Stored (..., B, S, KH, hd), a cache carried through the layer
    scan is relaid out whole, heads first, at every step (and held
    twice). Spiking caches keep (..., B', S, KH, hd|words)."""
    return cfg.spiking is None


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               batch=None, params=None,
               chunk_headroom: int = 0) -> Dict[str, Any]:
    """``chunk_headroom``: extra ring slots for window caches when decode
    will be fed chunked-prefill bites wider than one token (pass
    max_chunk - 1; see _cache_len)."""
    dt = jnp.dtype(cfg.dtype)
    b = batch_size * (cfg.spiking.time_steps if cfg.spiking else 1)
    packed = _packed_kv(cfg)
    words = -(-cfg.head_dim // 32)

    def kv(n_layers, kind):
        s = _cache_len(cfg, kind, max_len, chunk_headroom)
        # validity tags carry a batch (slot) dimension: every slot has its
        # own timeline, so continuous batching can hold sequences at
        # different positions in the same cache (the serve orchestrator's
        # per-slot state; a freed slot is re-admitted with all tags -1)
        if packed:
            shape = (n_layers, b, s, cfg.num_kv_heads, words)
            return {"k": jnp.zeros(shape, jnp.uint32),
                    "v": jnp.zeros(shape, jnp.uint32),
                    "pos": jnp.full((n_layers, batch_size, s), -1, jnp.int32)}
        shape = (n_layers, b, cfg.num_kv_heads, s, cfg.head_dim) \
            if kv_heads_major(cfg) else \
            (n_layers, b, s, cfg.num_kv_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(shape, dt),
            "v": jnp.zeros(shape, dt),
            "pos": jnp.full((n_layers, batch_size, s), -1, jnp.int32),
        }

    if cfg.attn_type == "local_global":
        g = cfg.num_layers // cfg.global_every
        return {"local": kv(g * (cfg.global_every - 1), "window"),
                "global": kv(g, "full")}
    kind = "window" if cfg.attn_type == "swa" else "full"
    return {"layers": kv(cfg.num_layers, kind)}


def _put_kv(cache, new, lead, slots, heads_major: bool):
    """Write new (B', C, KH, hd) at each row's slots (B', C) of cache
    (*lead, B', S, KH, hd), or (*lead, B', KH, S, hd) ``heads_major``;
    slots == S (padding) are dropped."""
    rows = jnp.arange(new.shape[0])[:, None]
    if heads_major:
        heads = jnp.arange(new.shape[2])[None, :, None]
        return cache.at[lead + (rows[:, :, None], heads,
                                slots[:, None, :])].set(
            new.swapaxes(1, 2), mode="drop")
    return cache.at[lead + (rows, slots)].set(new, mode="drop")


def _scatter_rows(cache, new, slots):
    """Per-row cache write: cache (B', S, ...), new (B', C, ...), slots
    (B', C) int32 — row b writes new[b, i] at cache[b, slots[b, i]].
    Out-of-range slot indices (== S, the padding sentinel) are dropped, so
    padded chunk positions never touch the cache."""
    return jax.vmap(lambda c, n, s: c.at[s].set(n, mode="drop"))(
        cache, new, slots)


def _decode_layer(p, cfg: ModelConfig, x, cache_l, pos, n_tok, kind: str,
                  layer=None):
    """One decode token or a chunked-prefill bite against this layer's KV
    cache, with a *per-slot* timeline.

    x: (B', C, D) — B' = B (dense) or T_s*B (spiking, time-major fold);
    cache_l: {'k','v','pos'} for this layer, pos tags shaped (B, S);
    pos: (B,) absolute position of x[:, 0] per slot;
    n_tok: (B,) count of real tokens per slot (rows are right-padded to
    the common chunk width C; padded positions are neither written to the
    cache nor tagged valid, so a decode slot rides a prefill wave at C=1
    cost in cache state).
    layer: where ``cache_l`` stacks many layers' caches, this layer's
    index into them: the bite is written into the stack in place, and the
    stack is returned.
    """
    b = pos.shape[0]
    b_rows, c = x.shape[0], x.shape[1]
    reps_t = b_rows // b                       # T_s in spiking mode, else 1
    tile = (lambda u: jnp.tile(u, (reps_t,) + (1,) * (u.ndim - 1))) \
        if reps_t > 1 else (lambda u: u)
    qpos = pos[:, None] + jnp.arange(c)        # (B, C) absolute q positions
    qpos_rows = tile(qpos)                     # (B', C)
    with _attn_scope(cfg, kind):
        h = nn.rmsnorm(p["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(p, cfg, h, qpos_rows, kind=kind)
        if cfg.spiking is not None:
            # T_s is folded into the batch dim; unfold for LIF dynamics
            # over time.
            t = cfg.spiking.time_steps

            def lif_t(u):
                u_t = u.reshape(t, -1, *u.shape[1:])
                s, _ = lif_scan(u_t, cfg.spiking)
                return s.reshape(-1, *u.shape[1:])
            q, k, v = lif_t(q), lif_t(k), lif_t(v)
        else:
            lif_t = None
        window = cfg.window if kind == "window" else None
        packed = _packed_kv(cfg)
        if packed:
            # spikes pack losslessly: K/V are {0,1} after the LIF, one uint32
            # word per 32 channels (the binary engine's spike-RAM layout)
            k, v = pack_bits(k), pack_bits(v)
        lead = () if layer is None else (layer,)
        heads_major = kv_heads_major(cfg)
        s_len = cache_l["pos"].shape[-1]
        # rolling write for window caches (== pos for full); chunk width must
        # not exceed the window, or a bite would overwrite its own entries
        slot = jnp.where(jnp.arange(c)[None, :] < n_tok[:, None],
                         qpos % s_len, s_len).astype(jnp.int32)  # (B, C)
        slot_rows = tile(slot)
        if layer is None and not heads_major:
            k_all = _scatter_rows(cache_l["k"], k, slot_rows)
            v_all = _scatter_rows(cache_l["v"], v, slot_rows)
        else:
            k_all, v_all = (_put_kv(cache_l[n], u, lead, slot_rows,
                                    heads_major)
                            for n, u in (("k", k), ("v", v)))
        if layer is None:
            pos_all = jax.vmap(
                lambda e, s, val: e.at[s].set(val, mode="drop"))(
                cache_l["pos"], slot, qpos.astype(jnp.int32))
        else:
            pos_all = cache_l["pos"].at[lead + (jnp.arange(b)[:, None],
                                                slot)].set(
                qpos.astype(jnp.int32), mode="drop")
        new_cache = {"k": k_all, "v": v_all, "pos": pos_all}
        k_cache, v_cache, entry_pos = (a[lead] for a in (k_all, v_all,
                                                         pos_all))
        if heads_major:
            k_cache, v_cache = k_cache.swapaxes(1, 2), v_cache.swapaxes(1, 2)
        if cfg.spiking is not None:
            qf = q.reshape(b_rows, c, cfg.num_kv_heads,
                           cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
            if packed:
                # AND-PopCount against the packed cache: exact integer overlap
                # counts, bit-identical to the fp32 dot on unpacked spikes
                qp = pack_bits(qf)                       # (B', C, KH, rep, W)
                kcT = k_cache.transpose(0, 2, 1, 3)      # (B', KH, S, W)
                counts = jax.lax.population_count(
                    qp[:, :, :, :, None, :] & kcT[:, None, :, None, :, :]).sum(
                    axis=-1).astype(jnp.int32)           # (B', C, KH, rep, S)
                sc = counts.astype(jnp.float32) / math.sqrt(cfg.head_dim)
            else:
                sc = jnp.einsum("bcgrd,bkgd->bcgrk", qf.astype(jnp.float32),
                                k_cache.astype(jnp.float32)) \
                    / math.sqrt(cfg.head_dim)
            a = binarize(sc, p["delta"], cfg.spiking.surrogate_alpha)
            valid = (entry_pos[:, None, :] >= 0) & \
                (entry_pos[:, None, :] <= qpos[:, :, None])       # (B, C, S)
            if window is not None:
                valid &= entry_pos[:, None, :] > qpos[:, :, None] - window
            a = jnp.where(tile(valid)[:, :, None, None, :], a, 0.0)
            vc = unpack_bits(v_cache, cfg.head_dim) if packed \
                else v_cache.astype(jnp.float32)
            attn = jnp.einsum("bcgrk,bkgd->bcgrd", a, vc)
            attn = attn.reshape(b_rows, c, cfg.q_dim).astype(x.dtype)
        else:
            attn = nn.decode_attention(q, k_cache, v_cache,
                                       entry_pos=entry_pos, cur_pos=qpos,
                                       window=window)
            attn = attn.reshape(b_rows, c, cfg.q_dim)
        x = x + nn.linear(p["wo"], attn)
    h2 = nn.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.spiking is not None:
        # mirror the full-seq spiking MLP (up -> LIF -> down, no gate/act)
        # so decode stays consistent with prefill token-for-token
        up = nn.linear(p["mlp"]["up"], h2)
        x = x + nn.linear(p["mlp"]["down"], lif_t(up))
    else:
        real = jnp.arange(c)[None, :] < n_tok[:, None]        # (B, C)
        x = x + _ffn(p, cfg, h2, valid=real)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, n_tok=None):
    """tokens: (B, C) int32 — one decode token per slot (C == 1) or a
    chunked-prefill bite; pos: scalar or (B,) int32, the absolute position
    of tokens[:, 0] per slot (a scalar broadcasts: all slots aligned, the
    pre-orchestrator contract); n_tok: optional (B,) count of real tokens
    per row when rows are right-padded to the common chunk width C.

    Returns (logits (B, C, V), new_cache).
    """
    b, c = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    n_tok = jnp.full((b,), c, jnp.int32) if n_tok is None \
        else jnp.asarray(n_tok, jnp.int32)
    x = nn.embed(params["embed"], tokens)
    if cfg.spiking is not None:
        t = cfg.spiking.time_steps
        x = jnp.broadcast_to(x[None], (t,) + x.shape).reshape(-1, *x.shape[1:])
    x = constrain(x, "batch", None, "embed")

    if cfg.attn_type == "local_global":
        g = cfg.num_layers // cfg.global_every
        n_local = cfg.global_every - 1

        # the caches ride the scan's carry, and each layer writes its bite
        # into them in place: as the scan's xs and ys they would be held
        # twice
        def group_body(carry, inp):
            x, c_loc, c_glob = carry
            gp, i = inp
            for j in range(cfg.global_every):
                sub = jax.tree_util.tree_map(lambda a: a[j], gp)
                if j < n_local:
                    x, c_loc = _decode_layer(sub, cfg, x, c_loc, pos, n_tok,
                                             "window", layer=i * n_local + j)
                else:
                    x, c_glob = _decode_layer(sub, cfg, x, c_glob, pos,
                                              n_tok, "full", layer=i)
            return (x, c_loc, c_glob), None

        (x, nl, ng), _ = jax.lax.scan(
            group_body, (x, cache["local"], cache["global"]),
            (params["groups"], jnp.arange(g)))
        new_cache = {"local": nl, "global": ng}
    else:
        kind = "window" if cfg.attn_type == "swa" else "full"

        def body(x, inp):
            lp, cl = inp
            x, nc = _decode_layer(lp, cfg, x, cl, pos, n_tok, kind)
            return x, nc
        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache = {"layers": new_layers}

    if cfg.spiking is not None:
        t = cfg.spiking.time_steps
        x = x.reshape(t, -1, *x.shape[1:]).mean(axis=0)
    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = nn.unembed(params["embed"], x)
    else:
        logits = nn.linear(params["lm_head"], x).astype(jnp.float32)
    return logits, new_cache


def invalidate_slots(cache, slot_mask):
    """Free masked slots for re-admission: every validity tag of a masked
    slot goes to -1, so the next occupant starts at position 0 attending
    over nothing — the previous request's K/V rows become unreachable
    (they are overwritten as the new sequence advances).

    slot_mask: (B,) bool. K/V payloads are left in place (tags alone gate
    attention), which keeps this a cheap tag-only write.
    """
    def fix(path, leaf):
        if getattr(path[-1], "key", None) == "pos":
            return jnp.where(slot_mask[None, :, None],
                             jnp.int32(-1), leaf)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)
