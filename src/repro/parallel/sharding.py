"""Logical-axis sharding rules (MaxText-style) + param-tree spec assignment.

Models annotate activations with *logical* axis names via :func:`constrain`;
a rules table maps logical names to mesh axes. Parameters get their
PartitionSpec from path-pattern rules per family (see :func:`param_specs`).

The production meshes (launch/mesh.py) are
  single-pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)

Default logical rules:
  batch   -> ('pod', 'data')   (DP; pod folds into DP)
  fsdp    -> 'data'            (param/optimizer FSDP shard axis)
  model   -> 'model'           (TP: heads / d_ff / vocab / experts)
  seq     -> None              (sequence usually replicated; SP shards it)
"""
from __future__ import annotations

import re
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "fsdp": "data",
    "model": "model",
    "seq": None,
    "seq_shard": "data",   # sequence-parallel shard (long-context KV)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "d_ff": "model",
    "vocab": "model",
    "experts": "model",
}


def set_rules(rules: Optional[Dict[str, Any]]) -> None:
    _state.rules = rules


def get_rules() -> Optional[Dict[str, Any]]:
    return getattr(_state, "rules", None)


class use_rules:
    """Context manager installing logical->mesh axis rules."""

    def __init__(self, rules: Optional[Dict[str, Any]]):
        self.rules = rules

    def __enter__(self):
        self.prev = get_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc):
        set_rules(self.prev)


def _mesh_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    if mesh is not None:
        return tuple(mesh.axis_names)
    env = jax.sharding.get_abstract_mesh()
    return tuple(env.axis_names) if env is not None else ()


def logical_spec(names: Sequence[Optional[str]],
                 rules: Optional[Dict[str, Any]] = None,
                 mesh: Optional[Mesh] = None) -> P:
    """Translate logical axis names to a PartitionSpec under ``rules``.

    Mesh axes that do not exist in the active mesh are dropped (so a
    single-pod mesh silently ignores the 'pod' component), and an axis used
    twice keeps only its first occurrence (PartitionSpec validity).
    """
    rules = rules if rules is not None else (get_rules() or {})
    avail = set(_mesh_axes(mesh))
    used: set = set()
    parts = []
    for name in names:
        axes = rules.get(name) if name else None
        if axes is None:
            parts.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if (not avail or a in avail) and a not in used)
        used.update(axes)
        parts.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*parts)  # keep positional trailing Nones


def rules_for_mesh(mesh: Mesh, **overrides) -> Dict[str, Any]:
    """DEFAULT_RULES bound to a concrete mesh (constrain() then emits
    NamedShardings — no ambient mesh context needed)."""
    rules = dict(DEFAULT_RULES, **overrides)
    rules["_mesh"] = mesh
    return rules


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without rules.

    Specs are fitted to the value's shape (axes that don't divide a dim
    are dropped), so the same model code works for batch=256 and batch=1.
    """
    rules = get_rules()
    if rules is None:
        return x
    mesh = rules.get("_mesh")
    spec = logical_spec(names, rules, mesh=mesh)
    spec = fit_spec_to_shape(spec, x.shape, mesh)
    try:
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # outside mesh context (unit tests on CPU)


# ---------------------------------------------------------------------------
# Parameter spec assignment by path patterns
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_sizes(mesh: Optional[Mesh]) -> Dict[str, int]:
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def fit_spec_to_shape(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """Right-align a spec to ``shape`` and drop mesh axes that don't divide
    the dimension (e.g. vocab=51865 can't shard 16-way; 25 heads can't
    shard over model=16 — they fall back to replicated on that dim)."""
    ndim = len(shape)
    parts = list(spec)
    if len(parts) > ndim:
        parts = parts[len(parts) - ndim:]
    if len(parts) < ndim:
        parts = [None] * (ndim - len(parts)) + parts
    sizes = _axis_sizes(mesh)
    out = []
    for dim, part in zip(shape, parts):
        if part is None:
            out.append(None)
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        kept, prod = [], 1
        for a in axes:
            n = sizes.get(a, None)
            if n is None and sizes:
                continue
            n = n or 1
            if dim % (prod * n) == 0:
                kept.append(a)
                prod *= n
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_specs(params: Any, pattern_rules: Sequence[Tuple[str, P]],
                default: P = P(), mesh: Optional[Mesh] = None) -> Any:
    """Map a param pytree to PartitionSpecs via ordered regex path rules.

    ``pattern_rules``: list of (regex, PartitionSpec); first match wins.
    Specs are right-aligned to each leaf's rank (scan-stacked params add a
    leading layer axis that stays unsharded) and validated against ``mesh``
    for divisibility (non-dividing axes are dropped per-dimension).
    """
    compiled = [(re.compile(rx), spec) for rx, spec in pattern_rules]

    def assign(path, leaf):
        ps = _path_str(path)
        shape = getattr(leaf, "shape", ())
        for rx, spec in compiled:
            if rx.search(ps):
                return fit_spec_to_shape(spec, shape, mesh)
        return fit_spec_to_shape(default, shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, params)


def shard_put(tree: Any, spec_tree: Any, mesh: Mesh) -> Any:
    """device_put a pytree onto ``mesh`` following a PartitionSpec tree
    (the host->mesh hand-off for serve: params and cache move once, the
    jitted step then keeps them resident in their shards)."""
    return jax.tree_util.tree_map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        spec_tree, tree,
        is_leaf=lambda s: isinstance(s, P))


def named_sharding_tree(specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, filter_spec_for_mesh(s, mesh)), specs,
        is_leaf=lambda s: isinstance(s, P))


def filter_spec_for_mesh(spec: P, mesh: Mesh) -> P:
    """Drop mesh-axis references that don't exist in ``mesh``."""
    avail = set(mesh.axis_names)
    parts = []
    for part in spec:
        if part is None:
            parts.append(None)
        elif isinstance(part, str):
            parts.append(part if part in avail else None)
        else:
            kept = tuple(a for a in part if a in avail)
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*parts)
