"""Serving-step builders: prefill and single-token decode, mesh-aware.

``serve_step`` for the decode shapes lowers decode (one new token against
a seq_len KV cache), NOT train, per the assignment.  Cache sharding uses
dist.sharding.auto_spec (batch over data axes, largest divisible dim —
the cache sequence/width dim — over 'model').
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    auto_spec,
    batch_specs,
    data_axes,
    divisible_axes,
    paged_spec,
    partition_params,
)
from repro.models.config import ArchConfig, ShapeSpec
from repro.models.paged_cache import PagedKV, PagedLatent
from repro.models.transformer import make_ctx

__all__ = ["build_prefill", "build_decode", "build_decode_paged",
           "prefill_batch_sds", "decode_inputs_sds", "cache_specs",
           "cache_sds", "paged_cache_sds"]


def prefill_batch_sds(cfg: ArchConfig, shape: ShapeSpec,
                      dtype=jnp.bfloat16) -> dict:
    b, s = shape.global_batch, shape.seq_len
    sds = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.family == "audio":
        sds["audio_emb"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_len, cfg.d_model), dtype)
    return sds


def cache_sds(model, cfg: ArchConfig, shape: ShapeSpec,
              dtype=jnp.bfloat16) -> Any:
    """Abstract decode cache via eval_shape (no allocation)."""
    ctx = make_ctx(None, "decode", cache_len=shape.seq_len)
    return jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, ctx, dtype))


def paged_cache_sds(model, n_pages: int, page_size: int,
                    dtype=jnp.bfloat16) -> Any:
    """Abstract paged-pool tree via eval_shape (no allocation)."""
    ctx = make_ctx(None, "decode", cache_len=0)
    return jax.eval_shape(
        lambda: model.init_paged_cache(n_pages, page_size, ctx, dtype))


def _is_pool(x: Any) -> bool:
    return isinstance(x, (PagedKV, PagedLatent))


def _segment_specs(tree: Any, mesh, *, batch_dim: int,
                   page_dim: int) -> Any:
    """Leaf specs for one cache segment: contiguous caches batch-shard
    via auto_spec, paged pools (batch-dim-free) page-shard via
    paged_spec — both 2D (data x model) on the same array."""
    def node_spec(node):
        if _is_pool(node):
            return jax.tree.map(
                lambda l: paged_spec(l.shape, mesh, page_dim=page_dim),
                node)
        return jax.tree.map(
            lambda l: auto_spec(l.shape, mesh, batch_dim=batch_dim),
            node)
    return jax.tree.map(node_spec, tree, is_leaf=_is_pool)


def cache_specs(cache_abstract: Any, mesh) -> Any:
    """Spec tree for decode caches (contiguous or paged).

    Scan-segment caches are stacked (R, B, ...) — batch (or the page
    dim, for paged pools) is dim 1; prefix/suffix (and whisper's plain
    list) caches have it at dim 0.
    """
    if isinstance(cache_abstract, dict) and "scan" in cache_abstract:
        return {
            "prefix": _segment_specs(cache_abstract["prefix"], mesh,
                                     batch_dim=0, page_dim=0),
            "scan": _segment_specs(cache_abstract["scan"], mesh,
                                   batch_dim=1, page_dim=1),
            "suffix": _segment_specs(cache_abstract["suffix"], mesh,
                                     batch_dim=0, page_dim=0),
        }
    return _segment_specs(cache_abstract, mesh, batch_dim=0, page_dim=0)


def decode_inputs_sds(model, cfg: ArchConfig, shape: ShapeSpec,
                      dtype=jnp.bfloat16) -> tuple:
    """(token, cache, pos) stand-ins for the decode serve_step."""
    b = shape.global_batch
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    cache = cache_sds(model, cfg, shape, dtype)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return token, cache, pos


def build_prefill(model, cfg: ArchConfig, shape: ShapeSpec, mesh,
                  tuner=None):
    """Returns (prefill_fn, param_specs, batch_specs, out description).

    ``tuner`` reaches every routine-aware call site through the Ctx, so
    a DispatchRecorder around the built function (or its jit trace)
    observes the prefill's routine mix — causal self-attention scores
    dispatch as SYRK, projections/MoE as GEMM.
    """
    ctx = make_ctx(mesh, "prefill", cache_len=shape.seq_len, remat=False,
                   tuner=tuner)

    if cfg.family == "audio":
        def prefill(params, batch):
            return model.prefill(params, batch, ctx)
    else:
        def prefill(params, batch):
            return model.prefill(params, batch["tokens"], ctx)

    if mesh is None:
        return prefill, None, None
    return (prefill, partition_params(model, cfg, mesh),
            batch_specs(cfg, shape, mesh))


def build_decode(model, cfg: ArchConfig, shape: ShapeSpec, mesh,
                 tuner=None):
    """Returns (decode_fn, param_specs, (token, cache, pos) specs).

    ``tuner`` reaches the decode call sites through the Ctx; the
    per-layer KV/latent cache updates dispatch as TRSM-adjacent events
    (sequential along the cache axis), observable by a recorder.
    """
    ctx = make_ctx(mesh, "decode", cache_len=shape.seq_len, tuner=tuner)

    def decode(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos, ctx)

    if mesh is None:
        return decode, None, None
    p_specs = partition_params(model, cfg, mesh)
    cache_abs = cache_sds(model, cfg, shape)
    c_specs = cache_specs(cache_abs, mesh)
    t_spec = P(divisible_axes(shape.global_batch, data_axes(mesh), mesh),
               None)
    return decode, p_specs, (t_spec, c_specs, P())


def build_decode_paged(model, cfg: ArchConfig, *, slots: int,
                       n_pages: int, page_size: int, table_pages: int,
                       mesh, tuner=None):
    """Paged twin of :func:`build_decode` for the continuous-batching
    scheduler's step shapes.

    Returns (decode_fn, param_specs, (token, pool, pos, table) specs).
    ``pos`` is (slots,) per-sequence positions and ``table``
    (slots, table_pages) the page table — both batch-sharded over the
    data axes; the pool is page-sharded 2D via cache_specs/paged_spec.
    """
    cache_len = table_pages * page_size
    ctx = make_ctx(mesh, "decode", cache_len=cache_len, tuner=tuner)

    def decode(params, token, pool, pos, table):
        return model.decode_step(params, token, pool, pos, ctx, table)

    if mesh is None:
        return decode, None, None
    p_specs = partition_params(model, cfg, mesh)
    pool_abs = paged_cache_sds(model, n_pages, page_size)
    pool_specs = cache_specs(pool_abs, mesh)
    slot_entry = divisible_axes(slots, data_axes(mesh), mesh)
    return decode, p_specs, (P(slot_entry, None), pool_specs,
                             P(slot_entry), P(slot_entry, None))
