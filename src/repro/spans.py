"""Named host spans in the JAX profiler's own trace.

``span(name, **meta)`` marks one boundary of the solver, named
``<layer>.<phase>`` (``partition.level``, ``device.find``, ...), with its
metadata (``level``, ``n``, ``mode``, ``kind``) as keywords::

    with span("partition.level", level=li, n=fine.n):
        ...

The span is a ``jax.profiler.TraceAnnotation``, so it lands on the host
plane of the profiler's trace, on the same clock as the device's events,
and a running profiler is the only switch: with none running a span costs
about as much as a ``nullcontext``.  This module never imports jax.  Where
jax is not loaded -- the numpy-only paths and the spawned worker processes
-- a span is one shared no-op context, and the process stays jax-free.

Spans mark phases, syncs and passes, never the body of a per-node or
per-edge loop.
"""
from __future__ import annotations

import contextlib
import sys

_OFF = contextlib.nullcontext()


def span(name: str, **meta):
    """A context that records ``name`` in a running profiler's trace."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **meta)
