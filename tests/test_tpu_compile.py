"""The main-path Pallas kernels compile for a TPU v5e at published widths.

Interpret mode (the rest of the suite) cannot see Mosaic's rules: block
shapes that break the (8, 128) tiling, gathers it does not lower, VMEM
over the scoped limit. These tests hand each kernel to the TPU compiler
for a described (not attached) v5e chip, with ``interpret=False``, and
assert the compiled program holds the kernel (``tpu_custom_call``).
Nothing runs, so they say nothing about results or times.

Widths: spikingformer-8-512 (T=4, B=8, L=196 at 224x224, D=512, d_ff=2048,
8 heads x 64) and spikingformer-lm (T=4, D=256, d_ff=1024, 8 causal heads
x 32, a 128-token prompt, 4 slots).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite's workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_layer import fused_layer
from repro.kernels.fused_ssa import fused_ssa
from repro.kernels.spike_attention import spike_attention
from repro.kernels.spike_decode import (gather_spike_matmul,
                                        quant_gather_spike_matmul)
from repro.kernels.spike_matmul import quant_spike_matmul, spike_matmul

T, B, L, D, H, HD, FF = 4, 8, 196, 512, 8, 64, 2048      # 8-512
M = T * B * L
LM = dict(t=4, b=4, l=128, d=256, h=8, hd=32, ff=1024)   # spikingformer-lm
PROJECTIONS = [(D, D), (D, FF), (FF, D)]                 # Q/K/V, MLP up/down


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


f32, i8 = jnp.float32, jnp.int8


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_tile_spike_matmul(one_chip, k, n):
    _compile(lambda s, w: spike_matmul(s, w, out_dtype=f32,
                                       interpret=False),
             one_chip, ((M, k), f32), ((k, n), f32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_quant_spike_matmul(one_chip, k, n):
    _compile(lambda s, w, sc: quant_spike_matmul(s, w, sc,
                                                 interpret=False),
             one_chip, ((M, k), f32), ((k, n), i8), ((n,), f32))


def test_quant_spike_matmul_counts_lanes(one_chip):
    """wo under int8 weights: binary-attention counts ride int32 lanes,
    split into int8 digits for the MXU (no int32 matmul in Mosaic)."""
    _compile(lambda s, w, sc, b: quant_spike_matmul(
        s, w, sc, bias=b, counts=True, interpret=False),
        one_chip, ((M, D), f32), ((D, D), i8), ((D,), f32), ((D,), f32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_gather_spike_matmul(one_chip, k, n):
    _compile(lambda s, w, b: gather_spike_matmul(s, w, bias=b,
                                                 interpret=False),
             one_chip, ((M, k), f32), ((k, n), f32), ((n,), f32))


def test_quant_gather_spike_matmul(one_chip):
    _compile(lambda s, w, sc: quant_gather_spike_matmul(
        s, w, sc, counts=True, interpret=False),
        one_chip, ((M, D), f32), ((D, D), i8), ((D,), f32))


@pytest.mark.parametrize("bh,l,hd,causal", [(T * B * H, L, HD, False),
                                            (4 * 4 * 8, 128, 32, True)])
def test_spike_attention(one_chip, bh, l, hd, causal):
    _compile(lambda q, k, v: spike_attention(
        q, k, v, scale=hd ** -0.5, delta=0.5, causal=causal,
        interpret=False),
        one_chip, *[((bh, l, hd), f32)] * 3)


def _family(family):
    if family == "bn":
        return dict(t=T, b=B, l=L, d=D, h=H, hd=HD, ff=FF)
    return LM


@pytest.mark.parametrize("family", ["bn", "rope"])
def test_fused_ssa(one_chip, family):
    c = _family(family)
    q = c["h"] * c["hd"]
    aux = (3, 4, q) if family == "bn" else (2, c["l"], c["hd"] // 2)
    _compile(lambda x, w3, a: fused_ssa(
        x, w3, None, a, 0.5, family=family, num_heads=c["h"],
        head_dim=c["hd"], scale=c["hd"] ** -0.5, causal=family == "rope",
        interpret=False)[0],
        one_chip, ((c["t"], c["b"], c["l"], c["d"]), f32),
        ((3, c["d"], q), f32), (aux, f32))


@pytest.mark.parametrize("family", ["bn", "rope"])
@pytest.mark.parametrize("overlap", ["fused", "pipeline"])
@pytest.mark.parametrize("sparse", ["tile", "decoded"])
def test_fused_layer(one_chip, family, overlap, sparse):
    c = _family(family)
    t, b, l, d, q, ff = c["t"], c["b"], c["l"], c["d"], c["h"] * c["hd"], \
        c["ff"]
    shapes = [((t, b, l, d), f32)] * 2 + [
        ((3, d, q), f32), ((q, d), f32), ((d, ff), f32), ((ff, d), f32)]
    if family == "bn":
        shapes += [((3, 4, q), f32), ((4, d), f32), ((4, ff), f32),
                   ((4, d), f32)]
    else:
        shapes += [((2, l, c["hd"] // 2), f32), ((1, d), f32)]

    def step(x, s, w3, wo, w1, w2, auxp, auxo, *aux12):
        aux1, aux2 = aux12 if aux12 else (None, None)
        return fused_layer(
            x, s, w3, wo, w1, w2, None, auxp, auxo, aux1, aux2, 0.5,
            family=family, num_heads=c["h"], head_dim=c["hd"],
            scale=c["hd"] ** -0.5, causal=family == "rope", sparse=sparse,
            pipeline=overlap == "pipeline", interpret=False)[0]

    _compile(step, one_chip, *shapes)
