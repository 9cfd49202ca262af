"""Every phase of the compiled eval step carries a program scope.

The benchmark's per-layer device times (``chip_bench/metrics/``) join a
trace's ops to the ``op_name`` metadata of the compiled program
(``chip_bench.trace.hlo_scopes``) and sum them by scope prefix. A recorded
trace cannot notice a scope that a later change drops; these tests can.
They compile the jitted eval step at SMOKE size on the CPU and read its
``op_name``s through the same parser the reduction uses:

* every instruction traced from the step (an ``op_name`` under ``jit(``;
  parameters carry their argument path instead) has a component from the
  program's scopes;
* where the layer program runs as XLA ops (``overlap`` ``off``, the
  configs' default), the sparse engine's projections, the binary
  engine's attention and the LIF scans each appear inside
  ``dual_engine.fused_layer``;
* with ``overlap='fused'`` the layer kernel sits inside
  ``dual_engine.fused_layer``, and the stem, the neurons and the head
  keep their own scopes;
* with the LIFs on the one-pass kernel (the chip's branch of
  ``lif_scan``, run here in interpret mode) every op stays scoped, and
  all LIF work sits in ``lif.kernel`` inside ``lif.scan``;
* ``disable_annotations`` compiles the same step with none of them;
* a program loaded from the persistent compilation cache carries its own
  scopes, not those of a program that differs from it only in scopes.
"""
import jax
import jax.numpy as jnp
import pytest

from chip_bench.trace import hlo_scopes
from repro.configs import get_config
from repro.core import engine as E
from repro.launch import steps
from repro.launch.compile_cache import setup_compile_cache
from repro.models import registry

PROGRAM_SCOPES = ("sps.", "spikingformer.", "transformer.", "lif.",
                  "sparse_engine.", "binary_engine.", "dual_engine.")


def _op_names(arch, overlap=None):
    """op_names of the instructions traced from ``arch``'s compiled eval
    step at SMOKE size (``overlap`` overrides the config's)."""
    cfg = get_config(arch, smoke=True)
    if overlap is not None:
        cfg = cfg.replace(engine=cfg.engine.replace(overlap=overlap))
    params = jax.eval_shape(lambda: registry.init(cfg, jax.random.PRNGKey(0)))
    if cfg.vision is not None:
        v = cfg.vision
        batch = {"images": jax.ShapeDtypeStruct(
            (2, v.img_size, v.img_size, v.in_channels), jnp.float32)}
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = jax.jit(steps.build_prefill_step(cfg)).lower(
        params, batch).compile().as_text()
    names = [n for n in hlo_scopes(text).values() if n.startswith("jit(")]
    assert names
    return names


def _parts(name):
    return name.split("/")


def _has(name, *prefixes):
    return any(p.startswith(prefixes) for p in _parts(name))


def _under_layer(names, prefix):
    """Whether some op sits in ``prefix`` inside dual_engine.fused_layer."""
    for n in names:
        parts = _parts(n)
        if "dual_engine.fused_layer" in parts:
            inner = parts[parts.index("dual_engine.fused_layer") + 1:]
            if any(p.startswith(prefix) for p in inner):
                return True
    return False


CASES = [("spikingformer-8-512", "off"), ("spikingformer-8-512", "fused"),
         ("spikingformer-4-256", None), ("spikingformer-lm", None)]


@pytest.mark.parametrize("arch,overlap", CASES)
def test_every_traced_op_is_scoped(arch, overlap):
    names = _op_names(arch, overlap)
    unscoped = sorted({n for n in names if not _has(n, *PROGRAM_SCOPES)})
    assert not unscoped, unscoped


@pytest.mark.parametrize("arch,overlap", [c for c in CASES
                                          if c[1] != "fused"])
def test_engines_and_neurons_inside_layer_program(arch, overlap):
    names = _op_names(arch, overlap)
    for prefix in ("sparse_engine.", "binary_engine.", "lif."):
        assert _under_layer(names, prefix), prefix
    # engine scopes never wrap work outside the layer program
    stray = {n for n in names
             if _has(n, "sparse_engine.", "binary_engine.")
             and "dual_engine.fused_layer" not in _parts(n)}
    assert not stray, sorted(stray)


def test_fused_layer_kernel_and_vision_phases():
    names = _op_names("spikingformer-8-512", "fused")
    assert any("dual_engine.fused_layer" in _parts(n) for n in names)
    for scope in ("sps.stem", "spikingformer.blocks", "spikingformer.head"):
        assert any(scope in _parts(n) for n in names), scope
    # the stem's and the head's neurons, and each block's input LIF
    assert any(_has(n, "sps.") and _has(n, "lif.") for n in names)
    assert any(_has(n, "spikingformer.head") and _has(n, "lif.")
               for n in names)
    assert any(_has(n, "spikingformer.blocks") and _has(n, "lif.")
               and not _has(n, "dual_engine.") for n in names)


def test_one_pass_lifs_keep_scopes(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_one_pass", lambda v0: True)
    names = _op_names("spikingformer-8-512", "off")
    unscoped = sorted({n for n in names if not _has(n, *PROGRAM_SCOPES)})
    assert not unscoped, unscoped
    lif = [_parts(n) for n in names if _has(n, "lif.")]
    assert lif and all("lif.kernel" in p and "lif.scan" in p
                       and p.index("lif.scan") < p.index("lif.kernel")
                       for p in lif), sorted({"/".join(p) for p in lif})


def test_disable_annotations_drops_every_scope():
    with E.disable_annotations():
        names = _op_names("spikingformer-8-512", "off")
    assert not [n for n in names if _has(n, *PROGRAM_SCOPES)]


def test_cached_program_keeps_its_own_scopes(tmp_path, monkeypatch):
    """The same computation under two scope names: the second compile,
    served from the persistent cache, still reads its own op_names."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        setup_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()

        def compiled(scope):
            def f(x):
                with E.annotate(scope):
                    return jnp.sin(x) * 2
            text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
            return set(hlo_scopes(text).values())

        assert any("parent.scope" in n for n in compiled("parent.scope"))
        names = compiled("change.scope")
        assert any("change.scope" in n for n in names)
        assert not any("parent.scope" in n for n in names)
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


NEW_SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine",
              "attn.window", "attn.full")


def _serve_step_text(arch):
    """The batched serve step's compiled HLO at SMOKE size, width 4."""
    cfg = get_config(arch, smoke=True)
    params = jax.eval_shape(lambda: registry.init(cfg, jax.random.PRNGKey(0)))
    headroom = 0 if cfg.attn_type == "full" else 3
    cache = jax.eval_shape(lambda: registry.init_cache(
        cfg, 3, 32, chunk_headroom=headroom))
    tok = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    vec = jax.ShapeDtypeStruct((3,), jnp.int32)
    return jax.jit(steps.build_batched_serve_step(cfg)).lower(
        params, cache, tok, vec, vec).compile().as_text()


def _without_metadata(text):
    import re
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(l for l in text.splitlines()
                     if l.startswith(("  ", "ENTRY", "%", "ROOT", "}")))


def test_expert_and_attention_scopes_in_the_serve_step():
    """mellum2's serve step names its expert layer's four phases and both
    kinds of attention layer; the spiking LM's step carries none of
    them, and its program is the same with every scope turned off."""
    names = set(hlo_scopes(_serve_step_text("mellum2-12b-a2.5b")).values())
    for scope in NEW_SCOPES:
        assert any(scope in _parts(n) for n in names), scope
    # the grouped matmuls sit in moe.experts, inside the layer loop
    assert any("moe.experts" in _parts(n) and "while" in n for n in names)
    text = _serve_step_text("spikingformer-lm")
    sflm = set(hlo_scopes(text).values())
    assert not [n for n in sflm if _has(n, *NEW_SCOPES)]
    with E.disable_annotations():
        bare = _serve_step_text("spikingformer-lm")
    assert _without_metadata(bare) == _without_metadata(text)
