"""Seeded weights, made by the benchmark and not by the program.

One jitted call per configuration draws the whole parameter tree on the
device, in the dtype it is served in. The tree follows the layout that the
program's models take (``spikingformer`` and the spiking ``dense`` LM);
``check_layout`` holds it against the program's own abstract tree, so a
program change to that layout stops the run instead of feeding it wrong
names. The plain references read the same tree by name.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# BatchNorm scale of the vision model. With weights of variance 1/fan_in
# and a {0,1} input of density p, a projection has variance p; a scale of
# about 2.5 brings a layer's currents back near unit variance, so spikes
# keep firing through eight blocks (a trained BatchNorm does the same).
VISION_BN_SCALE = 2.5


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds pass 2**32)."""
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _bn(key, n, dtype, lead=()):
    k1, k2 = jax.random.split(key)
    scale = VISION_BN_SCALE * (1.0 + 0.1 * jax.random.normal(
        k1, lead + (n,), jnp.float32))
    bias = 0.1 * jax.random.normal(k2, lead + (n,), jnp.float32)
    return {"scale": scale.astype(dtype), "bias": bias.astype(dtype)}


def vision_params(c: dict, key):
    dt = jnp.dtype(c["dtype"])
    d, ff, qd, nl = c["d_model"], c["d_ff"], c["num_heads"] * c["head_dim"], \
        c["num_layers"]
    ks = iter(jax.random.split(key, 32))
    chans = [c["in_channels"]] + list(c["sps_channels"])
    sps = [{"conv": {"w": _normal(next(ks), (3, 3, chans[i], chans[i + 1]),
                                  1.0 / math.sqrt(9 * chans[i]), dt)},
            "bn": _bn(next(ks), chans[i + 1], dt)} for i in range(4)]

    def lin(k_in, n_out):
        return {"w": _normal(next(ks), (nl, k_in, n_out),
                             1.0 / math.sqrt(k_in), dt)}
    blocks = {
        "wq": lin(d, qd), "wk": lin(d, qd), "wv": lin(d, qd),
        "wo": lin(qd, d),
        "bn_q": _bn(next(ks), qd, dt, (nl,)),
        "bn_k": _bn(next(ks), qd, dt, (nl,)),
        "bn_v": _bn(next(ks), qd, dt, (nl,)),
        "bn_o": _bn(next(ks), d, dt, (nl,)),
        "delta": jnp.full((nl,), c["attn_threshold_init"], jnp.float32),
        "w1": lin(d, ff), "bn_1": _bn(next(ks), ff, dt, (nl,)),
        "w2": lin(ff, d), "bn_2": _bn(next(ks), d, dt, (nl,)),
    }
    head = {"w": _normal(next(ks), (d, c["vocab_size"]), 1.0 / math.sqrt(d),
                         dt),
            "b": _normal(next(ks), (c["vocab_size"],), 0.01, dt)}
    return {"sps": sps, "blocks": blocks, "head": head}


def lm_params(c: dict, key):
    dt = jnp.dtype(c["dtype"])
    d, ff, nl, v = c["d_model"], c["d_ff"], c["num_layers"], c["vocab_size"]
    qd = c["num_heads"] * c["head_dim"]
    kvd = c["num_kv_heads"] * c["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def lin(k_in, n_out, std=None):
        std = 1.0 / math.sqrt(k_in) if std is None else std
        return {"w": _normal(next(ks), (nl, k_in, n_out), std, dt)}

    def norm(lead=(nl,)):
        return {"scale": (1.0 + 0.1 * jax.random.normal(
            next(ks), lead + (d,), jnp.float32)).astype(dt)}
    layers = {
        "ln1": norm(), "wq": lin(d, qd), "wk": lin(d, kvd), "wv": lin(d, kvd),
        "wo": lin(qd, d, 1.0 / math.sqrt(qd * 2 * nl)), "ln2": norm(),
        "mlp": {"up": lin(d, ff), "down": lin(ff, d)},
        "delta": jnp.full((nl,), c["attn_threshold_init"], jnp.float32),
    }
    return {"embed": {"table": _normal(next(ks), (v, d), 1.0 / math.sqrt(d),
                                       dt)},
            "final_norm": norm(()),
            "layers": layers,
            "lm_head": {"w": _normal(next(ks), (d, v), 1.0 / math.sqrt(d),
                                     dt)}}


MAKERS = {"spikingformer": vision_params, "spiking_lm": lm_params}


def make_params(c: dict, seed: int):
    """The whole tree on the device, in one jitted call."""
    maker = functools.partial(MAKERS[c["family"]], c)
    return jax.jit(maker)(jax_key(seed))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(abstract)
    if got != want:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"  benchmark {got}\n  program   {want}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree_util.tree_leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: benchmark "
                             f"{a.shape} {a.dtype}, program {b.shape} "
                             f"{b.dtype}")


def images(c: dict, seed: int, n: int):
    """``n`` seeded images (n, H, W, C) in the served dtype: a smooth
    random field (a 14 x 14 grid upsampled, the scale of an object) plus
    pixel noise, normalised per channel as ImageNet inputs are."""
    size, ch = c["img_size"], c["in_channels"]
    coarse = max(1, size // 16)

    def draw(key):
        k1, k2 = jax.random.split(key)
        low = jax.random.normal(k1, (n, coarse, coarse, ch), jnp.float32)
        img = jax.image.resize(low, (n, size, size, ch), "bilinear")
        img = img + 0.3 * jax.random.normal(k2, img.shape, jnp.float32)
        mu = img.mean(axis=(1, 2), keepdims=True)
        sd = img.std(axis=(1, 2), keepdims=True) + 1e-6
        return ((img - mu) / sd).astype(jnp.dtype(c["dtype"]))
    return jax.jit(draw)(jax_key(seed))


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    """Host RNG for one purpose (``stream``) of one run."""
    return np.random.default_rng([int(seed) % 2**64, stream])
