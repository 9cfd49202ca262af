"""Spikingformer + CIFAR-Net — the paper's evaluated workloads (§V-A).

Spikingformer (arXiv:2304.11954) with binary attention (Shen et al. [17]):
SPS conv stem -> encoder blocks (SSA + MLP) -> classification head, with
*pre-neuron residuals* (membrane currents are added, spikes stay the only
conv/linear inputs — Table I's preferred high-accuracy/high-efficiency
combination, which is what FireFly-T accelerates).

CIFAR-Net: the spiking conv network of FireFly v2 (Table IV footnote 3).

Execution: activations carry a leading time axis (T, B, ...); every
Conv/Linear consumes spikes from a LIF neuron; BatchNorm carries running
stats through a `state` tree (threaded by the train loop).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.scopes import annotate
from repro.core.spiking import SpikingConfig, binarize, lif_scan
from repro.parallel.sharding import constrain
from . import nn

# CIFAR-Net conv spec: (channels, pool) per layer; pool in {'', 'mp', 'ap'}
CIFARNET_SPEC: Tuple[Tuple[int, str], ...] = (
    (32, ""), (256, ""), (256, "mp"), (256, ""), (256, ""), (256, "mp"),
    (512, "mp"), (1024, "ap"))


def _sps_channels(cfg: ModelConfig) -> List[int]:
    d = cfg.d_model
    return [max(8, d // 8), max(8, d // 4), max(16, d // 2), d]


def _sps_pools(cfg: ModelConfig) -> List[bool]:
    n = 4
    stages = cfg.vision.sps_stages
    return [i >= n - stages for i in range(n)]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    return {
        "wq": nn.linear_init(ks[0], d, cfg.q_dim, dtype=dt),
        "wk": nn.linear_init(ks[1], d, cfg.q_dim, dtype=dt),
        "wv": nn.linear_init(ks[2], d, cfg.q_dim, dtype=dt),
        "wo": nn.linear_init(ks[3], cfg.q_dim, d, dtype=dt),
        "bn_q": nn.batchnorm_init(cfg.q_dim, dt),
        "bn_k": nn.batchnorm_init(cfg.q_dim, dt),
        "bn_v": nn.batchnorm_init(cfg.q_dim, dt),
        "bn_o": nn.batchnorm_init(d, dt),
        "delta": jnp.asarray(cfg.spiking.attn_threshold_init, jnp.float32),
        "w1": nn.linear_init(ks[4], d, cfg.d_ff, dtype=dt),
        "bn_1": nn.batchnorm_init(cfg.d_ff, dt),
        "w2": nn.linear_init(ks[5], cfg.d_ff, d, dtype=dt),
        "bn_2": nn.batchnorm_init(d, dt),
    }


def _block_state(cfg: ModelConfig):
    return {"bn_q": nn.batchnorm_state_init(cfg.q_dim),
            "bn_k": nn.batchnorm_state_init(cfg.q_dim),
            "bn_v": nn.batchnorm_state_init(cfg.q_dim),
            "bn_o": nn.batchnorm_state_init(cfg.d_model),
            "bn_1": nn.batchnorm_state_init(cfg.d_ff),
            "bn_2": nn.batchnorm_state_init(cfg.d_model)}


def init(cfg: ModelConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "cifarnet":
        return _init_cifarnet(cfg, key)
    ks = jax.random.split(key, 3 + 4)
    chans = [cfg.vision.in_channels] + _sps_channels(cfg)
    sps = []
    for i in range(4):
        sps.append({"conv": nn.conv2d_init(ks[i], chans[i], chans[i + 1],
                                           dtype=dt),
                    "bn": nn.batchnorm_init(chans[i + 1], dt)})
    keys = jax.random.split(ks[4], cfg.num_layers)
    return {
        "sps": sps,
        "blocks": jax.vmap(lambda k: _block_init(k, cfg))(keys),
        "head": nn.linear_init(ks[5], cfg.d_model, cfg.vocab_size, bias=True,
                               dtype=dt),
    }


def init_state(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family == "cifarnet":
        return {"convs": [nn.batchnorm_state_init(c)
                          for c, _ in CIFARNET_SPEC]}
    chans = _sps_channels(cfg)
    stacked = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a),
        *[_block_state(cfg) for _ in range(cfg.num_layers)])
    return {"sps": [nn.batchnorm_state_init(c) for c in chans],
            "blocks": stacked}


def _init_cifarnet(cfg: ModelConfig, key):
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, len(CIFARNET_SPEC) + 1)
    convs = []
    c_in = cfg.vision.in_channels
    for i, (c, _) in enumerate(CIFARNET_SPEC):
        convs.append({"conv": nn.conv2d_init(keys[i], c_in, c, dtype=dt),
                      "bn": nn.batchnorm_init(c, dt)})
        c_in = c
    return {"convs": convs,
            "head": nn.linear_init(keys[-1], c_in, cfg.vocab_size, bias=True,
                                   dtype=dt)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _lif(x, cfg: ModelConfig):
    s, _ = lif_scan(x, cfg.spiking)
    return s


def _fold_t(f, x, *args, **kw):
    """Apply f over (T*B, ...) by folding the time axis."""
    t = x.shape[0]
    y = f(x.reshape(-1, *x.shape[2:]), *args, **kw)
    return y.reshape(t, -1, *y.shape[1:])


def _sps(params, state, cfg: ModelConfig, images, train: bool):
    """images: (B, H, W, C) -> (tokens (T, B, L, D), new sps state)."""
    t = cfg.spiking.time_steps
    x = jnp.broadcast_to(images[None], (t,) + images.shape)  # direct coding
    pools = _sps_pools(cfg)
    new_state = []
    for i, p in enumerate(params["sps"]):
        x = _fold_t(lambda u: nn.conv2d(p["conv"], u), x)
        xf = x.reshape(-1, *x.shape[2:])
        yf, st = nn.batchnorm(p["bn"], state["sps"][i], xf, train=train)
        new_state.append(st)
        x = yf.reshape(x.shape)
        if i < len(params["sps"]) - 1:
            x = _lif(x, cfg)                     # spikes feed the next conv
        if pools[i]:
            x = _fold_t(nn.maxpool2, x)
    tt, b, h, w, d = x.shape
    return x.reshape(tt, b, h * w, d), new_state


def _block(p, st, cfg: ModelConfig, x, train: bool):
    """One encoder layer. x: (T,B,L,D) membrane currents.

    The whole layer program — input LIF + SSA bundle + wo/bn_o +
    pre-neuron residuals + spiking MLP — is owned by the engine
    (core.engine.layer_step): with ``overlap='fused' | 'pipeline'`` both
    overlay halves run as one Pallas grid spanning the layer (Fig. 5,
    with the MLP phases riding the same wavefront), otherwise the engine
    composes the sequential reference (which still hands the SSA bundle
    to ssa_step, so bundle-level fusion survives a layer-level
    fallback). The model keeps only the scan plumbing.
    """
    from repro.core.engine import layer_step
    return layer_step(p, st, cfg, x, train=train)


def forward(params, cfg: ModelConfig, batch, *, train: bool = False,
            state: Optional[Dict] = None):
    """batch: {'images': (B, H, W, C)} -> (logits (B, classes), aux)."""
    if cfg.family == "cifarnet":
        return _forward_cifarnet(params, cfg, batch, train=train, state=state)
    state = state if state is not None else init_state(cfg)
    with annotate("sps.stem"):
        x, sps_state = _sps(params, state, cfg, batch["images"], train)
        x = constrain(x, None, "batch", "seq", "embed")

    block_fn = _block
    if cfg.remat and train:
        block_fn = jax.checkpoint(_block, static_argnums=(2, 4),
                                  policy=jax.checkpoint_policies.nothing_saveable)

    def body(x, inp):
        bp, bst = inp
        x, new_bst = block_fn(bp, bst, cfg, x, train)
        return x, new_bst
    # the scan's own stacking and slicing ops fall under the scope too
    with annotate("spikingformer.blocks"):
        x, blocks_state = jax.lax.scan(body, x,
                                       (params["blocks"], state["blocks"]))
    with annotate("spikingformer.head"):
        spikes = _lif(x, cfg)
        rate = spikes.astype(jnp.float32).mean(axis=(0, 2))   # (B, D)
        logits = nn.linear(params["head"],
                           rate.astype(x.dtype)).astype(jnp.float32)
        fire_rate = spikes.astype(jnp.float32).mean()
    new_state = {"sps": sps_state, "blocks": blocks_state}
    return logits, {"state": new_state, "fire_rate": fire_rate}


def _forward_cifarnet(params, cfg: ModelConfig, batch, *, train: bool,
                      state: Optional[Dict]):
    state = state if state is not None else init_state(cfg)
    t = cfg.spiking.time_steps
    images = batch["images"]
    x = jnp.broadcast_to(images[None], (t,) + images.shape)
    new_state = []
    for i, ((c, pool), p) in enumerate(zip(CIFARNET_SPEC, params["convs"])):
        x = _fold_t(lambda u: nn.conv2d(p["conv"], u), x)
        xf = x.reshape(-1, *x.shape[2:])
        yf, st = nn.batchnorm(p["bn"], state["convs"][i], xf, train=train)
        new_state.append(st)
        x = _lif(yf.reshape(x.shape), cfg)
        if pool == "mp":
            x = _fold_t(nn.maxpool2, x)
        elif pool == "ap":
            x = x.mean(axis=(2, 3))                            # (T, B, C)
    rate = x.astype(jnp.float32).mean(axis=0)                  # (B, C)
    logits = nn.linear(params["head"],
                       rate.astype(jnp.dtype(cfg.dtype))).astype(jnp.float32)
    return logits, {"state": {"convs": new_state},
                    "fire_rate": x.astype(jnp.float32).mean()}


def layer_sparsities(params, cfg: ModelConfig, batch, state=None):
    """Per-layer spike sparsity (Fig. 11 reproduction): returns a list of
    (layer_name, sparsity) measured on the given batch."""
    state = state if state is not None else init_state(cfg)
    out: List[Tuple[str, float]] = []
    if cfg.family == "cifarnet":
        t = cfg.spiking.time_steps
        x = jnp.broadcast_to(batch["images"][None],
                             (t,) + batch["images"].shape)
        for i, ((c, pool), p) in enumerate(zip(CIFARNET_SPEC,
                                               params["convs"])):
            x = _fold_t(lambda u: nn.conv2d(p["conv"], u), x)
            xf = x.reshape(-1, *x.shape[2:])
            yf, _ = nn.batchnorm(p["bn"], state["convs"][i], xf, train=False)
            x = _lif(yf.reshape(x.shape), cfg)
            out.append((f"conv{i}", float(1.0 - x.mean())))
            if pool == "mp":
                x = _fold_t(nn.maxpool2, x)
            elif pool == "ap":
                x = x.mean(axis=(2, 3))
        return out
    x, _ = _sps(params, state, cfg, batch["images"], train=False)
    out.append(("sps", float(1.0 - _lif(x, cfg).mean())))
    for i in range(cfg.num_layers):
        bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        bst = jax.tree_util.tree_map(lambda a: a[i], state["blocks"])
        s_in = _lif(x, cfg)
        out.append((f"block{i}.in", float(1.0 - s_in.mean())))
        x, _ = _block(bp, bst, cfg, x, train=False)
    return out
