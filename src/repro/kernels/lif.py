"""LIF neurons in one pass over T_s.

FireFly-T pipelines membrane accumulation across output channels so the
neuronal-dynamics module shrinks to a (P_Fx x P_Ts) grid. The TPU analogue:
each grid step holds a block of neurons for all T_s steps, the membrane
never leaves the chip, so HBM sees the input currents once and the output
spikes once, instead of a ``while`` loop that reads and writes the whole
membrane and one slice of spikes a step.

Layout: the kernel takes a view that is a bitcast of the producer's
buffer, with the two tiled minor dims ``(R, D)`` left whole. Currents
``(T, ..., R, D)`` are viewed as ``(T, P, R, D)``, which folds only the
dims above the last two, so any ``R`` works; a convolution's ``(T, B, H,
W, C)`` output with ``C`` a multiple of 128 as ``(H*W, T, B, C)``, the
order XLA stores it in. The grid runs over ``(cdiv(P, bp), cdiv(R, br),
cdiv(D, bd))``; a ragged last block is read padded and written masked by
Pallas. Inside a block a loop walks the ``bp`` slabs of ``(br, bd)``
neurons, each held across the unrolled T loop.

The arithmetic is ``core.spiking.lif_step``'s, op for op in the currents'
dtype from a zero membrane, so the spikes are bitwise those of the scan
computed one op at a time. The kernel writes spikes only; forward only:
``kernels.ops.lif_one_pass`` takes the final membrane and the gradient
from the scan.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# input bytes a grid step moves (padded to the (8, 128) tiling): large
# enough that the ~0.35 us a step costs is a few percent of its DMA time
BLOCK_BYTES = 2 << 20
# widest D a grid step takes; a wider D is cut into blocks this wide
MAX_BLOCK_D = 512


def _kernel(i_ref, s_ref, *, t_steps: int, decay: float, v_th: float,
            soft_reset: bool, t_axis: int):
    slab = i_ref.shape[2:]
    at = (lambda t, j: (t, j)) if t_axis == 0 else (lambda t, j: (j, t))

    def body(j, carry):
        u = jnp.zeros(slab, i_ref.dtype)
        for t in range(t_steps):
            u = decay * u + i_ref[at(t, j)]
            # the compare in f32: v5e compares no bf16 (a cast keeps the
            # sign and zero of the difference, so the spike is the same)
            s = ((u - v_th).astype(jnp.float32) >= 0).astype(u.dtype)
            u = u - s * v_th if soft_reset else u * (1.0 - s)
            s_ref[at(t, j)] = s
        return carry

    jax.lax.fori_loop(0, i_ref.shape[1 - t_axis], body, 0)


def _blocks(t: int, p: int, r: int, d: int,
            itemsize: int) -> Tuple[int, int, int]:
    """(bp, br, bd): the slabs of P, rows of R and width of D a grid step
    takes, about BLOCK_BYTES of input."""
    bd = d if d <= MAX_BLOCK_D else MAX_BLOCK_D
    sub = 8 * max(1, 4 // itemsize)           # sublanes of one (8, 128) tile
    row = t * (-(-bd // 128) * 128) * itemsize
    br = r if -(-r // sub) * sub * row <= BLOCK_BYTES else max(
        sub, BLOCK_BYTES // row // sub * sub)
    bp = max(1, min(p, BLOCK_BYTES // (-(-br // sub) * sub * row)))
    return bp, br, bd


def _call(x, *, t_axis, decay, v_th, soft_reset, interpret):
    """x: (T, P, R, D) (t_axis 0) or (P, T, R, D) (t_axis 1) -> spikes."""
    t, p = (x.shape[0], x.shape[1]) if t_axis == 0 else (x.shape[1],
                                                         x.shape[0])
    r, d = x.shape[2:]
    bp, br, bd = _blocks(t, p, r, d, x.dtype.itemsize)
    grid = (pl.cdiv(p, bp), pl.cdiv(r, br), pl.cdiv(d, bd))
    if t_axis == 0:
        block = pl.BlockSpec((t, bp, br, bd), lambda i, k, j: (0, i, k, j))
    else:
        block = pl.BlockSpec((bp, t, br, bd), lambda i, k, j: (i, 0, k, j))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        functools.partial(_kernel, t_steps=t, decay=decay, v_th=v_th,
                          soft_reset=soft_reset, t_axis=t_axis),
        grid=grid, in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, name="lif_one_pass",
    )(x)


def lif_forward(currents: jax.Array, *, decay: float, v_th: float = 1.0,
                soft_reset: bool = False,
                interpret: Optional[bool] = None) -> jax.Array:
    """Spikes of LIF neurons over the leading time axis, from a zero
    membrane, in one pass.

    Args:
      currents: ``(T, ...)`` input currents.

    Returns:
      spikes ``(T, ...)``, in the currents' dtype.
    """
    full = currents.shape
    # size-1 dims left out: XLA may place them anywhere in a layout, so
    # dropping them costs nothing, and a tile of R real rows beats one row
    t, shape = full[0], tuple(n for n in full[1:] if n != 1)
    currents = currents.reshape((t,) + shape)
    if len(shape) == 4 and shape[-1] % 128 == 0:
        # a (T, B, H, W, C) convolution output with C a multiple of 128:
        # XLA's TPU convolutions store it H, W major over (T*B, C) tiles,
        # so the (H*W, T, B, C) view is a bitcast of the conv's buffer,
        # where the T-major view would cost a relayout in and out
        perm, t_axis = (2, 3, 0, 1, 4), 1
        view = (shape[1] * shape[2], t, shape[0], shape[3])
    else:
        perm, t_axis = tuple(range(currents.ndim)), 0
        view = (t, math.prod(shape[:-2])) + ((1, 1) + shape)[-2:]
    s = _call(currents.transpose(perm).reshape(view), t_axis=t_axis,
              decay=decay, v_th=v_th, soft_reset=soft_reset,
              interpret=interpret)
    s = s.reshape([currents.shape[a] for a in perm])
    return s.transpose(np.argsort(perm)).reshape(full)
