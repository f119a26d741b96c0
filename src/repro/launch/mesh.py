"""Mesh construction: the one place this repo builds a device mesh.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required by the dry-run, whose XLA_FLAGS must
be set before the first jax initialisation.

Every axis is ``AxisType.Auto``: the models are written for GSPMD
propagation — shardings enter at the jit boundary (and through
``shard_map`` for the MoE paths) and XLA derives the rest.
``jax.make_mesh`` defaults to ``AxisType.Explicit`` since JAX 0.9, under
which every gather and einsum on a sharded operand would need its own
``out_sharding`` (the embedding lookup is the first to refuse).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "dp_axes", "tp_axis"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    ADSALA_TP overrides the model-axis degree (total chips preserved) —
    the §Perf hillclimb knob for shifting TP<->DP balance.
    """
    import os
    tp = int(os.environ.get("ADSALA_TP", "16"))
    if multi_pod:
        shape = (2, 512 // (2 * tp), tp)
        axes = ("pod", "data", "model")
    else:
        shape = (256 // tp, tp)
        axes = ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def tp_axis(mesh) -> str:
    return "model"
