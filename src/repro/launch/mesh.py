"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required for the dry-run's forced
512-device host platform to stay contained to launch/dryrun.py.

Every mesh is built with ``Auto`` axes: the repo places arrays with
NamedShardings from ``parallel/rules.py`` and lets the compiler propagate
the rest. ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a
gather such as ``nn.embed``'s ``jnp.take`` on a (vocab@model, d@data)
table has no output sharding and is refused.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
    the DCN/ICI-bridged data-parallel outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return _mesh((n,), ("data",))


def make_serve_mesh(data: int = 1, model: int = 1):
    """Serving mesh: request slots on 'data', attention heads / vocab on
    'model'. Sized explicitly (not all-local-devices) so the serve bench
    can sweep mesh shapes under a forced host device count."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"serve mesh {data}x{model} needs {data * model} "
                         f"devices, have {n} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N)")
    return _mesh((data, model), ("data", "model"))
