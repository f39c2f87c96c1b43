"""Production meshes.

Single pod: 16x16 = 256 chips ('data', 'model').
Multi-pod:  2x16x16 = 512 chips ('pod', 'data', 'model'); the 'pod' axis
carries only data parallelism (gradient all-reduce over DCI), matching how
multi-pod TPU training is deployed.

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` hints (``parallel.sharding.constrain``), which
``jax.make_mesh``'s default Explicit axes would turn into asserts.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))
