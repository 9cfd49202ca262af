"""Plain float32 reference of the spikingformer vision model.

Written from the configuration file alone, in straightforward
``jax.numpy`` at ``precision='highest'``: no kernels, no engine, no
batching tricks. It imports nothing of the program. What it computes:

- SPS stem: four 3x3 SAME convolutions without bias, each followed by
  BatchNorm (eval, running mean 0 and variance 1) and, for the first
  three, a LIF neuron; a 2x2 max pool after each of the last
  ``sps_stages`` stages. The image is fed to every time step (direct
  coding). The stem's last output stays analog: it is the residual
  stream that enters block 0.
- Each block, on the residual stream x (T, B, L, D):
  s = LIF(x); q, k, v = LIF(BN(s W)); scores = q k^T / sqrt(d_head);
  a = 1[scores - delta >= 0]; x1 = x + BN(a v W_o);
  x2 = x1 + BN(LIF(BN(LIF(x1) W_1)) W_2).
- Head: the LIF spikes of the last stream, averaged over T and L, times
  the head's weights plus its bias.

LIF: u = (1 - 1/tau) u + input; spike = 1[u - v_th >= 0]; hard reset to 0
(soft reset subtracts v_th).

``compute`` names the precision: ``"float32"`` (the reference) or a lower
one, in which every matmul and convolution takes its operands and every
activation, membrane and residual sum is stored, as a program computing in
that dtype would; products still accumulate in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def store(x, compute="float32"):
    """x rounded to the ``compute`` dtype, held in float32."""
    if compute == "float32":
        return x
    return x.astype(jnp.dtype(compute)).astype(F32)


def matmul(x, w, compute="float32"):
    a, b = store(x.astype(F32), compute), store(w.astype(F32), compute)
    return store(jnp.matmul(a, b, precision="highest",
                            preferred_element_type=F32), compute)


def lif(c, currents, compute="float32"):
    """currents (T, ...) float32 -> spikes (T, ...) float32."""
    decay = 1.0 - 1.0 / c["tau"]
    u = jnp.zeros_like(currents[0])
    out = []
    for t in range(currents.shape[0]):
        u = store(decay * u + currents[t], compute)
        s = (u - c["v_threshold"] >= 0).astype(F32)
        u = u - s * c["v_threshold"] if c["soft_reset"] else u * (1.0 - s)
        out.append(s)
    return jnp.stack(out)


def batchnorm(c, p, x, compute="float32"):
    scale = p["scale"].astype(F32) / math.sqrt(1.0 + c["bn_eps"])
    return store(x * scale + p["bias"].astype(F32), compute)


def conv3x3(x, w, compute):
    """x (N, H, W, C) float32, w (3, 3, C, O)."""
    a, b = store(x, compute), store(w.astype(F32), compute)
    return store(jax.lax.conv_general_dilated(
        a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision="highest", preferred_element_type=F32), compute)


def maxpool2(x):
    n, h, w, ch = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, ch).max(axis=(2, 4))


def stem(c, params, images, compute):
    t = c["time_steps"]
    b = images.shape[0]
    x = jnp.broadcast_to(images.astype(F32)[None], (t,) + images.shape)
    x = x.reshape(t * b, *images.shape[1:])
    densities = []
    for i, p in enumerate(params["sps"]):
        x = batchnorm(c, p["bn"], conv3x3(x, p["conv"]["w"], compute),
                      compute)
        if i < 3:
            s = lif(c, x.reshape(t, b, *x.shape[1:]), compute)
            densities.append(s.mean())
            x = s.reshape(x.shape)
        if i >= 4 - c["sps_stages"]:
            x = maxpool2(x)
    return x.reshape(t, b, -1, x.shape[-1]), densities


def block(c, p, x, compute):
    t, b, l, d = x.shape
    h, hd = c["num_heads"], c["head_dim"]

    def proj(s, name, bn):
        return batchnorm(c, p[bn], matmul(s, p[name]["w"], compute), compute)
    s = lif(c, x, compute)
    q, k, v = (lif(c, proj(s, w, bn), compute) for w, bn in
               (("wq", "bn_q"), ("wk", "bn_k"), ("wv", "bn_v")))
    heads = lambda u: u.reshape(t, b, l, h, hd).transpose(0, 1, 3, 2, 4)
    scores = matmul(heads(q), heads(k).swapaxes(-1, -2)) / math.sqrt(hd)
    attn = (scores - p["delta"] >= 0).astype(F32)
    ctx = matmul(attn, heads(v), compute).transpose(0, 1, 3, 2, 4).reshape(
        t, b, l, h * hd)
    x1 = store(x + proj(ctx, "wo", "bn_o"), compute)
    s2 = lif(c, x1, compute)
    hid = lif(c, proj(s2, "w1", "bn_1"), compute)
    x2 = store(x1 + proj(hid, "w2", "bn_2"), compute)
    return x2, [s.mean(), q.mean(), k.mean(), v.mean(), s2.mean(),
                hid.mean()]


def forward(c, params, images, compute="float32"):
    """images (B, H, W, C) -> (logits (B, classes) float32, densities):
    the spike density of every LIF neuron layer, in order."""
    x, densities = stem(c, params, images, compute)
    for i in range(c["num_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        x, dens = block(c, p, x, compute)
        densities += dens
    s = lif(c, x, compute)
    densities.append(s.mean())
    rate = store(s.mean(axis=(0, 2)), compute)
    logits = matmul(rate, params["head"]["w"], compute) \
        + params["head"]["b"].astype(F32)
    return logits, jnp.stack(densities)


def density_names(c):
    names = [f"sps{i}" for i in range(3)]
    for i in range(c["num_layers"]):
        names += [f"block{i}.{n}" for n in ("in", "q", "k", "v", "mid",
                                            "mlp")]
    return names + ["head"]
