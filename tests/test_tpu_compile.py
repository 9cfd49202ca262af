"""The main-path Pallas kernels compile for a TPU v5e at published widths.

Interpret mode (the rest of the suite) cannot see Mosaic's rules: block
shapes that break the (8, 128) tiling, gathers it does not lower, VMEM
over the scoped limit. These tests hand each kernel to the TPU compiler
for a described (not attached) v5e chip, with ``interpret=False``, and
assert the compiled program holds the kernel (``tpu_custom_call``).
Nothing runs, so they say nothing about results or times.

One more compile holds the eval step's profiler scopes to the chip's
fusions: a device trace names a fusion by its own ``op_name``, so every
fusion that runs a layer-program matmul must carry an engine scope.

Widths: spikingformer-8-512 (T=4, B=8, L=196 at 224x224, D=512, d_ff=2048,
8 heads x 64) and spikingformer-lm (T=4, D=256, d_ff=1024, 8 causal heads
x 32, a 128-token prompt, 4 slots).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite's workers all import
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_layer import fused_layer
from repro.kernels.fused_ssa import fused_ssa
from repro.kernels.spike_attention import spike_attention
from repro.kernels.spike_decode import (gather_spike_matmul,
                                        quant_gather_spike_matmul)
from repro.kernels.spike_matmul import quant_spike_matmul, spike_matmul
from repro.launch import steps
from repro.models import registry

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


_MATMUL = re.compile(r"\s(?:dot|convolution)\(")


def _matmul_sites(text):
    """op_names of the instructions that run a matmul or convolution on
    the device: each fusion whose computation holds one, and each such op
    outside a fusion."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            head = line.split()
            cur = comps.setdefault(head[head[0] == "ENTRY"].lstrip("%"), [])
        elif cur is not None:
            cur.append(line)
    fused = {m.group(1) for lines in comps.values() for line in lines
             for m in [re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)]
             if m}
    heavy = {n for n in fused if any(_MATMUL.search(x) for x in comps[n])}
    sites = []
    for name, lines in comps.items():
        for line in lines:
            op = re.search(r'op_name="([^"]*)"', line)
            call = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
            if op and ((call and call.group(1) in heavy)
                       or (name not in fused and _MATMUL.search(line))):
                sites.append(op.group(1))
    return sites


def test_eval_step_matmul_fusions_carry_engine_scopes(one_chip):
    """spikingformer-8-512's eval step (bf16, batch 8 at 224x224,
    ``overlap='auto'``, as the benchmark runs it): inside the layer
    program, every fusion holding a matmul sits in the sparse or binary
    engine's scope; outside it, in the stem's or the head's."""
    cfg = get_config("spikingformer-8-512")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: registry.init(cfg, jax.random.PRNGKey(0))))
    images = jax.ShapeDtypeStruct((B, 224, 224, 3), jnp.bfloat16,
                                  sharding=one_chip)
    text = jax.jit(steps.build_prefill_step(cfg)).lower(
        params, {"images": images}).compile().as_text()
    sites = [s.split("/") for s in _matmul_sites(text)]
    layer = [s for s in sites if "dual_engine.fused_layer" in s]
    engines = [[p for p in s if p.startswith(("sparse_engine.",
                                              "binary_engine."))]
               for s in layer]
    assert all(engines), [s for s, e in zip(layer, engines) if not e]
    # Q, K, V, wo, w1, w2 and the two attention matmuls
    assert sum(e[0].startswith("sparse_engine.") for e in engines) >= 6
    assert sum(e[0].startswith("binary_engine.") for e in engines) >= 2
    rest = [s for s in sites if "dual_engine.fused_layer" not in s]
    assert rest and all("sps.stem" in s or "spikingformer.head" in s
                        for s in rest)
