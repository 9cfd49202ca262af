"""Spiking neural dynamics: LIF neurons, surrogate gradients, binarization.

The paper's workloads are spiking transformers (Spikingformer family) trained
with BrainCog and deployed on FireFly-T. This module provides the neural
dynamics substrate:

* ``spike``            — Heaviside with a sigmoid surrogate gradient
                         (``custom_jvp`` so both fwd- and rev-mode work).
* ``lif_scan``         — multi-step Leaky Integrate-and-Fire over the time
                         axis, soft or hard reset: one pass over T on the
                         chip, ``lax.scan`` otherwise.
* ``binarize``         — learnable-threshold binarization used by binary
                         attention (Shen et al. [17] / BESTformer [18]).
* ``SpikingConfig``    — the knob models use to switch spiking mode on.

Parameterization notes (faithfulness): Spikingformer uses LIF with
``tau = 2.0`` (decay 0.5), threshold 1.0 and hard reset in SpikingJelly /
BrainCog; we default to the same but keep soft reset available (FireFly-T's
neuron module supports both; soft reset is what the accumulate-subtract
hardware in FireFly v2 implements).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.scopes import annotate


@dataclasses.dataclass(frozen=True)
class SpikingConfig:
    """Configuration for spiking execution of a model."""

    time_steps: int = 4          # T_s
    tau: float = 2.0             # membrane time constant; decay = 1 - 1/tau
    v_threshold: float = 1.0
    soft_reset: bool = False     # Spikingformer default: hard reset
    surrogate_alpha: float = 4.0
    attention: bool = True       # enable binary attention (the binary engine)
    attn_threshold_init: float = 0.3  # learnable Delta init for binarization
    binarize_scores: bool = True      # binarize QK^T (binary attention [17])
    binarize_context: bool = False    # additionally binarize (QK^T)V

    @property
    def decay(self) -> float:
        return 1.0 - 1.0 / self.tau


# ---------------------------------------------------------------------------
# Surrogate-gradient spike function
# ---------------------------------------------------------------------------

@partial(jax.custom_jvp, nondiff_argnums=(1,))
def spike(v: jax.Array, alpha: float = 4.0) -> jax.Array:
    """Heaviside step ``1[v >= 0]`` with sigmoid surrogate gradient.

    Forward: exact step function (binary output, same dtype as ``v``).
    Backward: d/dv sigmoid(alpha * v) = alpha * s * (1 - s).
    """
    return (v >= 0).astype(v.dtype)


@spike.defjvp
def _spike_jvp(alpha, primals, tangents):
    (v,), (dv,) = primals, tangents
    out = spike(v, alpha)
    s = jax.nn.sigmoid(alpha * v)
    grad = alpha * s * (1.0 - s)
    return out, grad * dv


def binarize(x: jax.Array, delta: jax.Array, alpha: float = 4.0) -> jax.Array:
    """Thresholded binarization ``1[x > delta]`` with surrogate gradient.

    ``delta`` is the learnable threshold of binary attention; gradients flow
    to both ``x`` and ``delta`` through the surrogate.
    """
    return spike(x - delta, alpha)


# ---------------------------------------------------------------------------
# LIF dynamics
# ---------------------------------------------------------------------------

def lif_step(u: jax.Array, x: jax.Array, *, decay: float, v_th: float,
             soft_reset: bool, alpha: float):
    """One LIF update. Returns (new_membrane, spikes)."""
    u = decay * u + x
    s = spike(u - v_th, alpha)
    if soft_reset:
        u = u - s * v_th
    else:
        u = u * (1.0 - s)
    return u, s


def lif_lax_scan(currents: jax.Array, cfg: SpikingConfig,
                 v0: Optional[jax.Array] = None):
    """``lif_scan`` as a ``lax.scan`` over T: the form off TPU, and the
    one whose surrogate gradient every form uses."""
    def step(u, x):
        u, s = lif_step(u, x, decay=cfg.decay, v_th=cfg.v_threshold,
                        soft_reset=cfg.soft_reset, alpha=cfg.surrogate_alpha)
        return u, s

    u0 = jnp.zeros_like(currents[0]) if v0 is None else v0
    u_final, spikes = jax.lax.scan(step, u0, currents)
    return spikes, u_final


def lif_scan(currents: jax.Array, cfg: SpikingConfig,
             v0: Optional[jax.Array] = None):
    """Run LIF dynamics over the leading time axis.

    ``kernels.ops.lif`` chooses the form: on the chip, from a zero
    membrane and outside a mesh, the spikes come from one pass over T
    (scope ``lif.kernel``); elsewhere, and under differentiation, from
    ``lif_lax_scan``. Both compute ``lif_step`` op for op.

    Args:
      currents: ``(T, ...)`` input currents.
      cfg: spiking configuration.
      v0: optional initial membrane ``(...)``; zeros if None.

    Returns:
      (spikes ``(T, ...)``, final membrane ``(...)``).
    """
    from repro.kernels import ops  # lazy: no cycle
    with annotate("lif.scan"):
        return ops.lif(currents, cfg, v0)


def lif_loop_reference(currents, cfg: SpikingConfig, v0=None):
    """Pure-python LIF loop — oracle for tests (identical math, no scan)."""
    u = jnp.zeros_like(currents[0]) if v0 is None else v0
    outs = []
    for t in range(currents.shape[0]):
        u, s = lif_step(u, currents[t], decay=cfg.decay, v_th=cfg.v_threshold,
                        soft_reset=cfg.soft_reset, alpha=cfg.surrogate_alpha)
        outs.append(s)
    return jnp.stack(outs), u


# ---------------------------------------------------------------------------
# Spike encodings
# ---------------------------------------------------------------------------

def rate_encode(x: jax.Array, time_steps: int, key: jax.Array) -> jax.Array:
    """Bernoulli rate coding: ``(...,) -> (T, ...)`` binary spikes."""
    p = jnp.clip(x, 0.0, 1.0)
    u = jax.random.uniform(key, (time_steps,) + x.shape, dtype=x.dtype)
    return (u < p).astype(x.dtype)


def direct_encode(x: jax.Array, time_steps: int) -> jax.Array:
    """Direct coding: replicate analog input across T (Spikingformer SPS
    input convention — the first conv layer consumes the analog image)."""
    return jnp.broadcast_to(x[None], (time_steps,) + x.shape)


def measure_sparsity(spikes: jax.Array) -> jax.Array:
    """Fraction of zero entries (the paper's Fig. 11 metric)."""
    return 1.0 - jnp.mean(spikes.astype(jnp.float32))
