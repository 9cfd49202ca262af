"""Profiler scopes for the program's phases.

Every phase a device trace should name carries one ``jax.named_scope``
through :func:`annotate`: the SPS stem (``sps.stem``), the block scan and
the head (``spikingformer.*``), each LIF scan (``lif.scan``), the sparse
engine's projections (``sparse_engine.*``), the binary engine's attention
(``binary_engine.*``) and the fused dual-engine steps (``dual_engine.*``).
A scope is HLO ``op_name`` metadata only: annotated and unannotated
programs compute bitwise-identical results (pinned by tests), and
:func:`disable_annotations` turns every scope off to prove exactly that.

A leaf module (it imports only JAX), so ``core/spiking`` and
``core/engine`` can both use it.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_state = threading.local()


def annotate(name: str) -> contextlib.AbstractContextManager:
    """Profiler scope ``name`` (``jax.named_scope``), unless annotations
    are disabled. Give ``name`` a dot (``lif.scan``): the trace reduction
    keys on dotted scope components."""
    if getattr(_state, "no_annotations", False):
        return contextlib.nullcontext()
    return jax.named_scope(name)


@contextlib.contextmanager
def disable_annotations():
    """Trace without profiler scopes (the bitwise test's control arm)."""
    prev = getattr(_state, "no_annotations", False)
    _state.no_annotations = True
    try:
        yield
    finally:
        _state.no_annotations = prev
