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

The one-pass LIF compiles at the benchmark cell's five LIF shapes (batch
64), and the compiled batch-64 eval step, steered onto the chip's branch,
runs every LIF as that kernel: no ``while`` under ``lif.``, and no more
relayouts (``copy``/``transpose``) next to its LIF custom calls than next
to the scans of the same step compiled the scan's way.

Widths: spikingformer-8-512 (T=4, B=8, L=196 at 224x224, D=512, d_ff=2048,
8 heads x 64) and spikingformer-lm (T=4, D=256, d_ff=1024, 8 causal heads
x 32, a 128-token prompt, 4 slots).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite's workers all import
this file.
"""
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_layer import fused_layer
from repro.kernels.lif import lif_forward
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
@pytest.mark.parametrize("sparse", ["tile", "decoded"])
def test_fused_layer(one_chip, family, sparse):
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
            interpret=False)[0]

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
    """spikingformer-8-512's eval step (bf16, batch 8 at 224x224, the
    config's engine as the benchmark runs it): inside the layer
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


LIF_SHAPES = [(4, 64, 224, 224, 64), (4, 64, 112, 112, 128),   # SPS stem
              (4, 64, 56, 56, 256), (4, 64, 196, 512),         # and blocks
              (4, 64, 196, 2048)]
# spikingformer-lm: a decode step of 4 slots, and a 2048-token prefill,
# whose rows R must be split to fit VMEM; a D cut raggedly
LIF_OTHER = [(4, 4, 1, 256), (4, 4, 2048, 256), (4, 8, 128, 700)]


@pytest.mark.parametrize("shape", LIF_SHAPES + LIF_OTHER)
def test_lif_one_pass(one_chip, shape):
    _compile(lambda x: lif_forward(x, decay=0.5, interpret=False),
             one_chip, (shape, jnp.bfloat16))


def _instructions(text):
    """name -> (opcode, result type, operand names, op_name) of every
    instruction of a compiled program's text."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*", line)
        if not m:
            continue
        rest, depth = line[m.end():], 0
        if rest.startswith("("):                 # a tuple's type
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if not depth:
                    break
            typ, rest = rest[:i + 1], rest[i + 1:].lstrip()
        else:
            typ, _, rest = rest.partition(" ")
        op = re.match(r"([\w\-]+)\(", rest)
        if not op:
            continue
        for i, ch in enumerate(rest[op.end() - 1:]):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        args = re.findall(r"%([\w.\-]+)", rest[op.end():op.end() + i])
        name = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = (op.group(1), typ, args,
                           name.group(1) if name else "")
    return out


def _relayouts_next_to(ins, sites):
    """Array copies and transposes that feed a site or read its result,
    through bitcasts and tuples."""
    users = collections.defaultdict(list)
    for n, (_, _, args, _) in ins.items():
        for a in args:
            users[a].append(n)
    found = set()

    def walk(n, step, seen):
        for m in step(n):
            if m in seen or m not in ins:
                continue
            seen.add(m)
            op, typ = ins[m][:2]
            if op in ("copy", "transpose") and "[]" not in typ:
                found.add(m)
            elif op in ("bitcast", "get-tuple-element", "tuple"):
                walk(m, step, seen)
    for site in sites:
        walk(site, lambda n: ins[n][2], set())
        walk(site, lambda n: users[n], set())
    return found


@pytest.fixture(scope="module")
def eval_steps(one_chip):
    """spikingformer-8-512's eval step at the cell's batch of 64, compiled
    with the LIFs one pass (the chip's branch of ``lif_scan``) and with
    the scan: {form: instructions}."""
    cfg = get_config("spikingformer-8-512")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: registry.init(cfg, jax.random.PRNGKey(0))))
    images = jax.ShapeDtypeStruct((64, 224, 224, 3), jnp.bfloat16,
                                  sharding=one_chip)
    out = {}
    for form, backend in (("one_pass", "tpu"), ("scan", None)):
        with pytest.MonkeyPatch.context() as mp:
            if backend:
                # what the chip runs: the code asks the backend it traces on
                mp.setattr(jax, "default_backend", lambda: backend)
            text = jax.jit(steps.build_prefill_step(cfg)).lower(
                params, {"images": images}).compile().as_text()
        out[form] = _instructions(text)
    return out


def _has_scope(op_name, prefix):
    return any(p.startswith(prefix) for p in op_name.split("/"))


def test_eval_step_lifs_run_one_pass(eval_steps):
    ins = eval_steps["one_pass"]
    loops = [n for n, (op, _, _, name) in ins.items()
             if op == "while" and _has_scope(name, "lif.")]
    assert not loops, loops
    calls = [n for n, (op, _, _, name) in ins.items()
             if op == "custom-call" and _has_scope(name, "lif.kernel")]
    # three in the stem, six in the block program, the head's
    assert len(calls) == 10, calls
    scans = [n for n, (op, _, _, name) in eval_steps["scan"].items()
             if op == "while" and _has_scope(name, "lif.scan")]
    assert len(scans) == 10, scans


def test_eval_step_lif_kernels_add_no_relayout(eval_steps):
    def relayouts(form, op):
        ins = eval_steps[form]
        sites = [n for n, (o, _, _, name) in ins.items()
                 if o == op and _has_scope(name, "lif.")]
        return sorted(_relayouts_next_to(ins, sites))
    one_pass = relayouts("one_pass", "custom-call")
    scan = relayouts("scan", "while")
    assert len(one_pass) <= len(scan), (one_pass, scan)


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
def test_grouped_matmul_lowers_at_mellum_shapes(one_chip, monkeypatch, k,
                                                n):
    """The expert layer's grouped matmul (megablox ``gmm`` at
    ``moe.GMM_TILE``) at mellum2.chat's widest wave: 1,024 rows x 8 picks
    into 8 held experts, gate/up (2304 -> 896) and down (896 -> 2304),
    whose K and N the tile does not divide."""
    from repro.models import moe
    # the chip's branch: the kernel runs interpreted off the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile(moe.grouped_matmul, one_chip,
                    ((8192, k), jnp.bfloat16), ((8, k, n), jnp.bfloat16),
                    ((8,), jnp.int32))
    assert "gmm" in text


@pytest.fixture(scope="module")
def mellum_serve_step(one_chip):
    """The mellum2.chat serve step at the cell's shape (64 slots of 4096
    positions, a width-16 wave, 8 held experts of each of 28 layers),
    compiled once: (config, its cache's bytes, the compiled step)."""
    cfg = get_config("mellum2-12b-a2.5b")
    slots, width = 64, 16

    def put(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        params = put(jax.eval_shape(lambda: registry.init(
            cfg, jax.random.PRNGKey(0))))
        cache = put(jax.eval_shape(lambda: registry.init_cache(
            cfg, slots, 4096, chunk_headroom=width - 1)))
        tok = jax.ShapeDtypeStruct((slots, width), jnp.int32,
                                   sharding=one_chip)
        vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
        compiled = jax.jit(steps.build_batched_serve_step(cfg),
                           donate_argnums=(1,)).lower(
            params, cache, tok, vec, vec).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    return cfg, cache_bytes, compiled


def test_mellum_serve_step_fits_one_chip(mellum_serve_step):
    """The serve step fits a v5e's 16 GB: the weights and the cache are
    its arguments, the cache is updated in place (aliased to the output),
    and no copy of the whole cache is made (temporaries well under one
    layer group's cache; a (B, S, KH, hd) cache carried through the layer
    scan was relaid out whole, 6.2 GB more). The grouped expert matmuls
    lower to Mosaic kernels, three a layer."""
    cfg, cache_bytes, compiled = mellum_serve_step
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes     # tags padded
    assert mem.temp_size_in_bytes < 1.0e9, mem
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15.0e9, mem
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 3 * cfg.global_every, len(calls)


def test_mellum_expert_layer_scatters_nothing(mellum_serve_step):
    """The expert layer combines each row's outputs with a gather through
    the inverse of its sort, and counts its groups: no scatter (which the
    TPU serialises where indices repeat, as a row's K pairs do) runs under
    a ``moe.`` scope, as an instruction or as a fusion the device trace
    names by one. Left aside: megablox ``gmm``'s own tile table, built
    from the eight group sizes under ``jit(gmm)``."""
    ins = _instructions(mellum_serve_step[2].as_text())
    moe_ops = {op for op, _, _, name in ins.values()
               if _has_scope(name, "moe.")}
    assert "gather" in moe_ops, moe_ops
    scatters = [n for n, (op, _, _, name) in ins.items()
                if _has_scope(name, "moe.") and "jit(gmm)" not in name
                and (op == "scatter"
                     or name.rsplit("/", 1)[-1].startswith("scatter"))]
    assert not scatters, scatters
