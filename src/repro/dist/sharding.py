"""Sharding subsystem: logical-axis rule tables + spec derivation.

The models declare parameters with *logical* axes ("vocab", "ff",
"heads", …, see :mod:`repro.models.params`); this module decides which
*physical* mesh axes carry each of them for a given (arch x mesh) cell —
the same decision the paper's tuner makes per GEMM (which chips, which
partition axis), lifted to whole parameter/activation trees.

Rule-table design
-----------------
Logical axes fall into three groups:

* ``MODEL_AXIS_RULES`` — weight dims that tensor-parallelism splits
  (vocab, ff, heads, kv_heads, expert_ff).  Candidate: the ``"model"``
  mesh axis.
* ``DATA_AXIS_RULES`` — dims carried by the data-parallel axes
  (``experts``: expert parallelism over ("pod", "data")).
* everything else (``embed``, ``layers``, ``lora``, unnamed) — always
  replicated.  ``embed`` is the contracted dim of every projection and
  ``lora`` ranks are small; replicating them keeps every PartitionSpec
  free of duplicate mesh axes by construction.

Every candidate is *divisibility-checked* against all dims that carry
the logical axis in the arch's actual ParamDef tree: a non-dividing
assignment is demoted (outermost axis dropped first, e.g.
``("pod", "data")`` -> ``("data",)``) or dropped to ``None`` entirely —
the GSPMD invariant that every sharded dim divides its mesh-axis
product.  mixtral's 8 experts on a 16-way data axis demote to ``None``
(its experts are split over the FF dim instead — ``expert_ff``), and
whisper's odd 51865-token vocab stays replicated.

Meshes are only read through ``.shape`` / ``.axis_names``, so a real
``jax.sharding.Mesh``, an ``AbstractMesh`` (see :func:`abstract_mesh`),
or any shape-shaped stand-in works — spec derivation never needs
devices.
"""

from __future__ import annotations

import math
from typing import Any, Iterable, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.params import ParamDef, param_specs

__all__ = [
    "TP_AXIS", "MODEL_AXIS_RULES", "DATA_AXIS_RULES",
    "abstract_mesh", "auto_spec", "batch_specs", "data_axes",
    "divisible_axes", "is_partition_spec", "logical_axis_dims",
    "named_shardings", "paged_spec", "param_rules", "partition_params",
    "state_specs",
]

#: the tensor-parallel mesh axis name (repro.launch.mesh convention)
TP_AXIS = "model"

#: logical axes whose dims tensor-parallelism splits
MODEL_AXIS_RULES = ("vocab", "ff", "heads", "kv_heads", "expert_ff")

#: logical axes carried by the data-parallel axes (expert parallelism)
DATA_AXIS_RULES = ("experts",)


def is_partition_spec(x: Any) -> bool:
    """Proper leaf test for PartitionSpec trees (no stringly class-name
    matching) — shared with :mod:`repro.ckpt.checkpoint`."""
    return isinstance(x, PartitionSpec)


def abstract_mesh(shape: dict[str, int]):
    """Device-free mesh stand-in from an ``{axis: size}`` dict — lets
    tests/benchmarks derive specs for 256/512-chip production meshes on
    a laptop."""
    from jax.sharding import AbstractMesh, AxisType
    return AbstractMesh(tuple(shape.values()), tuple(shape),
                        (AxisType.Auto,) * len(shape))


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != TP_AXIS)


def _axes_size(axes: Sequence[str], mesh) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def divisible_axes(dims: int | Iterable[int], axes: Sequence[str], mesh
                   ) -> str | tuple[str, ...] | None:
    """Largest demotion of ``axes`` whose size divides every dim.

    Drops axes outermost-first (``("pod", "data")`` -> ``("data",)``)
    until the remaining product divides all of ``dims``; returns a bare
    axis name for a single survivor, a tuple for several, or ``None``
    when nothing divides — i.e. an entry ready to drop into a
    PartitionSpec.
    """
    if isinstance(dims, int):
        dims = (dims,)
    dims = tuple(dims)
    axes = tuple(axes)
    while axes and any(d % _axes_size(axes, mesh) for d in dims):
        axes = axes[1:]
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def logical_axis_dims(defs: Any) -> dict[str, set[int]]:
    """Map each logical axis name to every dim size it tags in ``defs``."""
    out: dict[str, set[int]] = {}
    for d in jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef)):
        for dim, axis in zip(d.shape, d.axes):
            if axis is not None:
                out.setdefault(axis, set()).add(dim)
    return out


def param_rules(cfg, mesh, defs: Any = None) -> dict[str, Any]:
    """Logical-axis -> mesh-axis rule table for one (arch x mesh) cell.

    Divisibility-aware: every assignment is checked against all dims the
    axis tags in the arch's ParamDef tree and demoted/dropped so the
    resulting specs satisfy the GSPMD invariant on any mesh shape.
    ``defs`` may be supplied (e.g. ``model.defs``) to skip rebuilding
    the model.
    """
    if defs is None:
        from repro.configs import build_model
        defs = build_model(cfg).defs
    dims = logical_axis_dims(defs)
    dp = data_axes(mesh)
    rules: dict[str, Any] = {}
    for name, sizes in dims.items():
        if name in MODEL_AXIS_RULES and TP_AXIS in mesh.axis_names:
            rules[name] = divisible_axes(sizes, (TP_AXIS,), mesh)
        elif name in DATA_AXIS_RULES:
            rules[name] = divisible_axes(sizes, dp, mesh)
        else:
            rules[name] = None
    return rules


def partition_params(model, cfg, mesh) -> Any:
    """PartitionSpec tree for a model's parameters on ``mesh``."""
    return param_specs(model.defs, param_rules(cfg, mesh, model.defs))


def auto_spec(shape: Sequence[int], mesh, batch_dim: int = 0
              ) -> PartitionSpec:
    """Heuristic spec for an activation/cache array.

    The batch dim goes to the data-parallel axes (demoted until they
    divide, ``None`` if nothing does); the largest remaining dim
    divisible by the 'model' axis carries tensor parallelism; everything
    else is replicated.
    """
    entries: list[Any] = [None] * len(shape)
    entries[batch_dim] = divisible_axes(shape[batch_dim], data_axes(mesh),
                                        mesh)
    if TP_AXIS in mesh.axis_names:
        tp = mesh.shape[TP_AXIS]
        best = -1
        for i, d in enumerate(shape):
            if i == batch_dim or tp < 2 or d % tp:
                continue
            if best < 0 or d > shape[best]:
                best = i
        if best >= 0:
            entries[best] = TP_AXIS
    return PartitionSpec(*entries)


def paged_spec(shape: Sequence[int], mesh, page_dim: int = 0
               ) -> PartitionSpec:
    """Spec for a paged KV pool — 2D (data x model) on one array.

    Page pools (:mod:`repro.models.paged_cache`) carry no batch dim: the
    *page* dim is the parallel one, so it takes the data axes (demoted
    until they divide).  Tensor parallelism goes to the largest
    remaining dim divisible by 'model' — excluding the page-offset dim
    at ``page_dim + 1``: token slots within a page must stay whole on
    every shard or the page-table gather/scatter stops being local.
    Scan-stacked pools pass ``page_dim=1`` (dim 0 is the repeat dim,
    replicated like the 'layers' logical axis).
    """
    entries: list[Any] = [None] * len(shape)
    entries[page_dim] = divisible_axes(shape[page_dim], data_axes(mesh),
                                       mesh)
    if TP_AXIS in mesh.axis_names:
        tp = mesh.shape[TP_AXIS]
        best = -1
        for i, d in enumerate(shape):
            if i in (page_dim, page_dim + 1) or tp < 2 or d % tp:
                continue
            if best < 0 or d > shape[best]:
                best = i
        if best >= 0:
            entries[best] = TP_AXIS
    return PartitionSpec(*entries)


def batch_specs(cfg, shape, mesh) -> dict[str, PartitionSpec]:
    """Specs for one global batch (mirrors ``train_batch_sds`` /
    ``prefill_batch_sds`` key-for-key): batch over data axes, audio
    frame embeddings additionally over 'model' where divisible."""
    batch_entry = divisible_axes(shape.global_batch, data_axes(mesh), mesh)
    tok = PartitionSpec(batch_entry, None)
    specs = {"tokens": tok}
    if shape.kind == "train":
        specs["labels"] = tok
    if cfg.family == "audio":
        specs["audio_emb"] = auto_spec(
            (shape.global_batch, cfg.encoder_len, cfg.d_model), mesh,
            batch_dim=0)
    return specs


def state_specs(p_specs: Any, *, compress: bool = False) -> dict[str, Any]:
    """AdamW state specs derived mechanically from the param specs: the
    moments (and the error-feedback residual when gradient compression
    is on) mirror the parameter tree leaf-for-leaf, the step counter is
    replicated.  Layout keys come from :mod:`repro.train.optim` so the
    two can never drift."""
    from repro.train.optim import STATE_MOMENTS
    specs: dict[str, Any] = {"params": p_specs}
    for key in STATE_MOMENTS:
        specs[key] = p_specs
    specs["step"] = PartitionSpec()
    if compress:
        specs["ef"] = p_specs
    return specs


def named_shardings(mesh, specs: Any) -> Any:
    """PartitionSpec tree -> NamedSharding tree (``None`` passes through,
    for unconstrained outputs)."""
    if specs is None:
        return None
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=is_partition_spec)
