"""Mixture-of-Experts decoder LM (deepseek-moe-16b, kimi-k2-1t-a32b), and
the expert layer that the dense family takes in place of its MLP when
``cfg.moe`` is set (mellum2-12b-a2.5b).

An expert layer is told which experts it holds: ids ``[offset, offset +
held)`` of the router's ``MoEConfig.width``. It routes every token over
all of them and computes the part of the result that its own experts
give; what the others would add is left out (a chip's share of an
expert-parallel deployment).

Expert parallelism strategy (DESIGN.md §6): tokens are batch-sharded over
('pod','data') and *replicated* over 'model'; experts are sharded over
'model'. Each model-shard computes its local experts' contribution for all
of its tokens, then a psum over 'model' combines contributions. No
all-to-all, no one-hot dispatch matmuls (which are FLOP-hostile at 384
experts). Two dispatches: **dropless** (argsort by expert id → gather →
grouped matmul over the groups' real sizes → gather through the sort's
inverse and a weighted sum over each row's K picks), which every
serving path takes, and **sort-based capacity dispatch** (capacity-bounded
gather → batched expert matmul → scatter-add), which a training forward
takes where ``capacity_factor`` is set.

Dispatch runs inside ``shard_map`` when a mesh context is installed
(launch layer), and falls back to the identical single-shard code path
otherwise (unit tests).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.scopes import annotate
from repro.core.spiking import lif_scan
from repro.parallel.sharding import constrain, get_rules
from . import nn
from .transformer import _project_qkv, _attend_full_seq, _spike


# ---------------------------------------------------------------------------
# Mesh context for EP (installed by the launch layer)
# ---------------------------------------------------------------------------

import threading

_ctx = threading.local()


def set_ep_mesh(mesh, token_axes=("pod", "data"), expert_axis="model"):
    _ctx.mesh = mesh
    _ctx.token_axes = token_axes
    _ctx.expert_axis = expert_axis


def clear_ep_mesh():
    _ctx.mesh = None


def get_ep_mesh():
    return getattr(_ctx, "mesh", None), \
        getattr(_ctx, "token_axes", ("pod", "data")), \
        getattr(_ctx, "expert_axis", "model")


class use_ep_mesh:
    def __init__(self, mesh, token_axes=("pod", "data"), expert_axis="model"):
        self.args = (mesh, token_axes, expert_axis)

    def __enter__(self):
        self.prev = get_ep_mesh()
        set_ep_mesh(*self.args)

    def __exit__(self, *exc):
        set_ep_mesh(*self.prev)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def ffn_init(key, cfg: ModelConfig):
    """The router over all ``m.width`` experts; the weights of the
    ``m.num_experts`` held."""
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    e, d, f = m.num_experts, cfg.d_model, m.d_ff_expert
    std = 1.0 / math.sqrt(d)
    p = {
        "router": nn.normal(ks[0], (d, m.width), std, jnp.float32),
        "up": nn.normal(ks[1], (e, d, f), std, dt),
        "gate": nn.normal(ks[2], (e, d, f), std, dt),
        "down": nn.normal(ks[3], (e, f, d), 1.0 / math.sqrt(f), dt),
    }
    if m.num_shared:
        p["shared"] = nn.mlp_init(ks[4], d, m.num_shared * f, gated=True,
                                  dtype=dt)
    return p


def _attn_init(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
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
    if cfg.spiking is not None:
        p["delta"] = jnp.asarray(cfg.spiking.attn_threshold_init, jnp.float32)
    return p


def _moe_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = _attn_init(k1, cfg)
    p["moe"] = ffn_init(k2, cfg)
    return p


def _dense_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = _attn_init(k1, cfg)
    p["mlp"] = nn.mlp_init(k2, cfg.d_model, cfg.moe.first_dense_ff or cfg.d_ff,
                           gated=True, dtype=jnp.dtype(cfg.dtype))
    return p


def init(cfg: ModelConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    m = cfg.moe
    k_embed, k_dense, k_moe, k_head = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": nn.embedding_init(k_embed, cfg.vocab_size, cfg.d_model, dt),
        "final_norm": nn.rmsnorm_init(cfg.d_model, dt),
        "lm_head": nn.linear_init(k_head, cfg.d_model, cfg.vocab_size, dtype=dt),
    }
    if m.first_k_dense:
        keys = jax.random.split(k_dense, m.first_k_dense)
        params["dense_layers"] = jax.vmap(
            lambda k: _dense_layer_init(k, cfg))(keys)
    n_moe = cfg.num_layers - m.first_k_dense
    keys = jax.random.split(k_moe, n_moe)
    params["layers"] = jax.vmap(lambda k: _moe_layer_init(k, cfg))(keys)
    return params


# ---------------------------------------------------------------------------
# routing + dispatch
# ---------------------------------------------------------------------------


def router_topk(x2d: jax.Array, router_w: jax.Array, m: MoEConfig):
    """x2d: (T, D) -> (weights (T, K), idx (T, K), aux losses)."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.normalize_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balance loss + router z-loss
    me = probs.mean(axis=0)                                   # (E,)
    assign = jnp.zeros_like(probs).at[
        jnp.arange(x2d.shape[0])[:, None], idx].set(1.0).mean(axis=0)
    aux_lb = m.width * jnp.sum(me * assign)
    aux_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return w.astype(jnp.float32), idx, aux_lb, aux_z


def _local_expert_ffn(xg: jax.Array, up, gate, down, act) -> jax.Array:
    """xg: (E_loc, C, D) -> (E_loc, C, D); batched expert matmuls (MXU)."""
    h = jnp.einsum("ecd,edf->ecf", xg, up,
                   preferred_element_type=xg.dtype)
    g = jnp.einsum("ecd,edf->ecf", xg, gate,
                   preferred_element_type=xg.dtype)
    h = nn.activation(act)(g) * h
    return jnp.einsum("ecf,efd->ecd", h, down,
                      preferred_element_type=xg.dtype)


def _dispatch_local(x2d, w, idx, up, gate, down, m: MoEConfig, act: str,
                    e_local: int, local_offset) -> jax.Array:
    """Sort-based capacity dispatch for the local expert slice.

    x2d (T, D); w/idx (T, K); expert weights (E_loc, ...). Tokens routed to
    non-local experts are ignored here (another shard owns them).
    """
    t, d = x2d.shape
    k = m.top_k
    cap = max(1, int(math.ceil(t * k / m.width * m.capacity_factor)))

    flat_e = idx.reshape(-1)                        # (T*K,) global expert ids
    local_e = flat_e - local_offset
    is_local = (local_e >= 0) & (local_e < e_local)
    sort_key = jnp.where(is_local, local_e, e_local)
    order = jnp.argsort(sort_key, stable=True)
    sorted_e = sort_key[order]
    sorted_tok = (jnp.arange(t * k) // k)[order]
    sorted_w = w.reshape(-1)[order]

    counts = jnp.bincount(sorted_e, length=e_local + 1)[:e_local]
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)])[:e_local]
    slot = offsets[:, None] + jnp.arange(cap)[None, :]        # (E_loc, C)
    valid = jnp.arange(cap)[None, :] < jnp.minimum(counts, cap)[:, None]
    slot = jnp.clip(slot, 0, t * k - 1)
    tok_of_slot = sorted_tok[slot]                            # (E_loc, C)
    w_of_slot = jnp.where(valid, sorted_w[slot], 0.0)

    xg = jnp.take(x2d, tok_of_slot.reshape(-1), axis=0).reshape(
        e_local, cap, d)
    yg = _local_expert_ffn(xg, up, gate, down, act)
    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[tok_of_slot.reshape(-1)].add(
        (yg.astype(jnp.float32) * w_of_slot[..., None]).reshape(-1, d))
    return out.astype(x2d.dtype)


# (rows, K, N) tile of the grouped matmul: the fastest of those measured
# at mellum2.chat's expert shapes on a v5e (PERF.md), and twice as
# fast there as jax.lax.ragged_dot's own TPU kernel
GMM_TILE = (128, 768, 896)


def grouped_matmul(lhs, rhs, sizes):
    """lhs (M, K), its rows sorted by group, M a multiple of
    ``GMM_TILE[0]``; rhs (G, K, N); sizes (G,) int32 -> (M, N): each
    group's rows times its matrix (the Pallas kernel ``megablox.gmm``,
    interpreted off the TPU). Rows past ``sizes.sum()`` are left
    undefined."""
    tm, tk, tn = GMM_TILE
    return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
               tiling=(tm, min(tk, rhs.shape[1]), min(tn, rhs.shape[2])),
               interpret=jax.default_backend() != "tpu")


def _dispatch_dropless(x2d, w, idx, up, gate, down, act: str, offset,
                       valid) -> jax.Array:
    """Every (row, expert) pair that falls on a held expert, none dropped.

    x2d (T, D); w/idx (T, K) renormalised weights and global expert ids;
    expert weights (E_held, ...) for ids [offset, offset + E_held);
    valid (T,) bool: a row where it is False (padding) joins no group.
    The pairs are sorted by expert and the SwiGLU runs as grouped matmuls
    over the groups' real sizes. Each row then gathers its K outputs
    through the inverse of the sort (no scatter: a row's pairs collide)
    and sums them, each times its weight, in float32.
    """
    t, d = x2d.shape
    k = idx.shape[-1]
    e_held = up.shape[0]
    # a row picks distinct experts, so at most min(K, E_held) of its pairs
    # are held: the held pairs sort into the first T * that, rounded up to
    # the grouped matmul's row tile
    tm = GMM_TILE[0]
    m = -(-t * min(k, e_held) // tm) * tm
    with annotate("moe.dispatch"):
        local = idx - offset
        held = (local >= 0) & (local < e_held) & valid[:, None]
        key = jnp.where(held, local, e_held).reshape(-1)      # (T*K,)
        key = jnp.pad(key, (0, max(0, m - t * k)), constant_values=e_held)
        perm = jnp.argsort(key, stable=True)
        # inv[t * K + j]: the place in the sorted order of row t's j-th pick
        inv = jnp.argsort(perm)[:t * k]
        order = perm[:m]
        # a compare and a sum: bincount would scatter, each add colliding
        sizes = jnp.sum(key[:, None] == jnp.arange(e_held), axis=0,
                        dtype=jnp.int32)
        row = jnp.minimum(order // k, t - 1)   # pad pairs: any real row
        real = key[order] < e_held
        # rows outside every group are zero: the kernel leaves those rows
        # of its outputs undefined, and their gradients must not reach x
        xs = jnp.where(real[:, None], jnp.take(x2d, row, axis=0), 0)
    with annotate("moe.experts"):
        h = nn.activation(act)(grouped_matmul(xs, gate, sizes)) \
            * grouped_matmul(xs, up, sizes)
        ys = grouped_matmul(h, down, sizes)
    with annotate("moe.combine"):
        # a held pair sorts before sizes.sum(); every other pair points
        # past ys and reads zeros, so no undefined row of ys is read
        at = jnp.where(held.reshape(-1), inv, m)
        yk = jnp.take(ys, at, axis=0, mode="fill", fill_value=0)
        out = jnp.sum(yk.reshape(t, k, d).astype(jnp.float32)
                      * w[..., None], axis=1)
    return out.astype(x2d.dtype)


def moe_ffn(p, x: jax.Array, cfg: ModelConfig, *, train: bool = False,
            valid: Optional[jax.Array] = None,
            offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    """x: (..., S, D) -> (y, aux_loss): the part of the expert layer that
    the experts held in ``p`` give, ids ``[offset, offset + held)``.
    ``valid`` (x.shape[:-1] bool): rows that join no expert where False.
    A training forward with ``capacity_factor`` set drops what overflows
    an expert's capacity; every other call is dropless. EP via shard_map
    when mesh is set, each shard's offset from its axis index."""
    m = cfg.moe
    lead = x.shape[:-1]
    mesh, token_axes, expert_axis = get_ep_mesh()
    capacity = train and m.capacity_factor > 0
    if valid is None:
        valid = jnp.ones(lead, bool)

    def run(x_loc, router_w, up, gate, down, valid_loc, *, e_local, offset,
            in_map):
        x2d = x_loc.reshape(-1, x_loc.shape[-1])
        with annotate("moe.router"):
            w, idx, aux_lb, aux_z = router_topk(x2d, router_w, m)
        if capacity:
            y = _dispatch_local(x2d, w, idx, up, gate, down, m, cfg.act,
                                e_local, offset)
        else:
            y = _dispatch_dropless(x2d, w, idx, up, gate, down, cfg.act,
                                   offset, valid_loc.reshape(-1))
        aux = m.router_aux_weight * aux_lb + m.router_z_weight * aux_z
        if in_map:
            # combine expert contributions across the EP axis in bf16 —
            # halves the dominant model-axis all-reduce (§Perf K1)
            y = jax.lax.psum(y.astype(x_loc.dtype), expert_axis)
            axes = tuple(a for a in token_axes if a in mesh.axis_names)
            if axes:
                aux = jax.lax.pmean(aux, axes)
        return y.reshape(x_loc.shape), aux

    if mesh is None:
        y, aux = run(x, p["router"], p["up"], p["gate"], p["down"], valid,
                     e_local=m.num_experts, offset=offset, in_map=False)
    else:
        ep_size = mesh.shape[expert_axis]
        e_local = m.num_experts // ep_size
        axes = tuple(a for a in token_axes if a in mesh.axis_names)
        tok_spec = P(axes, *([None] * (x.ndim - 1)))
        valid_spec = P(axes, *([None] * (valid.ndim - 1)))

        def mapped(x_loc, router_w, up, gate, down, valid_loc):
            shard_offset = offset + jax.lax.axis_index(expert_axis) * e_local
            return run(x_loc, router_w, up, gate, down, valid_loc,
                       e_local=e_local, offset=shard_offset, in_map=True)

        y, aux = jax.shard_map(
            mapped, mesh=mesh,
            in_specs=(tok_spec, P(), P(expert_axis), P(expert_axis),
                      P(expert_axis), valid_spec),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(x, p["router"], p["up"], p["gate"], p["down"], valid)

    if m.num_shared:
        y = y + nn.mlp(p["shared"], x, cfg.act)
    return y, aux


# ---------------------------------------------------------------------------
# layers / forward / decode
# ---------------------------------------------------------------------------


def _attn_block(p, cfg: ModelConfig, x, positions, train: bool):
    h = nn.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _project_qkv(p, cfg, h, positions, repeat_kv=True)
    if cfg.spiking is not None:
        t = x.shape[0]
        q, k, v = (_spike(u, cfg, t) for u in (q, k, v))
        fold = lambda u: u.reshape(-1, *u.shape[2:])
        attn = _attend_full_seq(cfg, "full", fold(q), fold(k), fold(v),
                                delta=p["delta"])
        attn = attn.reshape(*x.shape[:-1], cfg.q_dim)
    else:
        attn = _attend_full_seq(cfg, "full", q, k, v)
        attn = attn.reshape(*x.shape[:-1], cfg.q_dim)
    return x + nn.linear(p["wo"], constrain(attn, "batch", "seq", "model"))


def _moe_layer(p, cfg: ModelConfig, x, positions, train: bool):
    x = _attn_block(p, cfg, x, positions, train)
    h = nn.rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, aux = moe_ffn(p["moe"], h, cfg, train=train)
    # name the expert output so the remat policy can SAVE it: recomputing
    # the expert FFN in bwd would re-gather the FSDP-sharded expert
    # weights a 3rd time (§Perf K4)
    y = checkpoint_name(y, "moe_out")
    return constrain(x + y, "batch", "seq", "embed"), aux


def _dense_layer(p, cfg: ModelConfig, x, positions, train: bool):
    x = _attn_block(p, cfg, x, positions, train)
    h = nn.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return constrain(x + nn.mlp(p["mlp"], h, cfg.act), "batch", "seq", "embed")


def forward(params, cfg: ModelConfig, batch, *, train: bool = False,
            inputs_embeds: Optional[jax.Array] = None):
    tokens = batch["tokens"]
    x = nn.embed(params["embed"], tokens) if inputs_embeds is None \
        else inputs_embeds
    x = constrain(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[-2])
    if cfg.spiking is not None:
        x = jnp.broadcast_to(x[None], (cfg.spiking.time_steps,) + x.shape)

    dense_fn, moe_fn = _dense_layer, _moe_layer
    if cfg.remat and train:
        dense_fn = jax.checkpoint(dense_fn, static_argnums=(1, 4),
                                  policy=jax.checkpoint_policies.nothing_saveable)
        moe_fn = jax.checkpoint(
            moe_fn, static_argnums=(1, 4),
            policy=jax.checkpoint_policies.save_only_these_names("moe_out"))

    if cfg.moe.first_k_dense:
        def dbody(x, lp):
            return dense_fn(lp, cfg, x, positions, train), None
        x, _ = jax.lax.scan(dbody, x, params["dense_layers"])

    def body(x, lp):
        x, aux = moe_fn(lp, cfg, x, positions, train)
        return x, aux
    x, auxes = jax.lax.scan(body, x, params["layers"])

    if cfg.spiking is not None:
        x = x.mean(axis=0)
    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = nn.linear(params["lm_head"], x).astype(jnp.float32)
    return constrain(logits, "batch", "seq", "vocab"), \
        {"moe_aux": jnp.sum(auxes)}


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               batch=None, params=None) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    b = batch_size * (cfg.spiking.time_steps if cfg.spiking else 1)

    def kv(n_layers):
        return {
            "k": jnp.zeros((n_layers, b, max_len, cfg.num_kv_heads,
                            cfg.head_dim), dt),
            "v": jnp.zeros((n_layers, b, max_len, cfg.num_kv_heads,
                            cfg.head_dim), dt),
            "pos": jnp.full((n_layers, max_len), -1, jnp.int32),
        }
    cache = {"layers": kv(cfg.num_layers - cfg.moe.first_k_dense)}
    if cfg.moe.first_k_dense:
        cache["dense_layers"] = kv(cfg.moe.first_k_dense)
    return cache


def _decode_attn(p, cfg: ModelConfig, x, cache_l, pos):
    h = nn.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _project_qkv(p, cfg, h, jnp.full((1,), pos))
    s_len = cache_l["k"].shape[1]
    slot = pos % s_len
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache_l["k"], k, slot, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache_l["v"], v, slot, 1)
    entry_pos = jax.lax.dynamic_update_slice_in_dim(
        cache_l["pos"], jnp.full((1,), pos, jnp.int32), slot, 0)
    attn = nn.decode_attention(q, k_cache, v_cache, entry_pos=entry_pos,
                               cur_pos=pos)
    x = x + nn.linear(p["wo"], attn.reshape(x.shape[0], 1, cfg.q_dim))
    return x, {"k": k_cache, "v": v_cache, "pos": entry_pos}


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    x = nn.embed(params["embed"], tokens)
    x = constrain(x, "batch", None, "embed")
    new_cache = {}

    if cfg.moe.first_k_dense:
        def dbody(x, inp):
            lp, c = inp
            x, nc = _decode_attn(lp, cfg, x, c, pos)
            h = nn.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            x = x + nn.mlp(lp["mlp"], h, cfg.act)
            return x, nc
        x, nd = jax.lax.scan(dbody, x,
                             (params["dense_layers"], cache["dense_layers"]))
        new_cache["dense_layers"] = nd

    def body(x, inp):
        lp, c = inp
        x, nc = _decode_attn(lp, cfg, x, c, pos)
        h = nn.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = moe_ffn(lp["moe"], h, cfg)
        return x + y, nc
    x, nl = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    new_cache["layers"] = nl

    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = nn.linear(params["lm_head"], x).astype(jnp.float32)
    return logits, new_cache
