"""Paged KV cache format: the device page pools and their primitives.

Paging (vLLM, arXiv 2309.06180) breaks a sequence's cache into
fixed-size **pages** of ``page_size`` token slots; a **page table** maps
each sequence's logical pages to physical pages of a shared pool.  This
module owns how a cache lives in pages; the host-side free list that
hands pages out is :class:`repro.serve.kv_cache.PageAllocator`.

* :class:`PagedKV` / :class:`PagedLatent` — registered pytrees holding
  one attention layer's page pool: ``(n_pages, page_size, width)``
  arrays, the direct paged analogue of
  :class:`~repro.models.layers.KVCache` and
  :class:`~repro.models.mla.MLACache`.  The layers of a layer scan keep
  their pools stacked, ``(L, n_pages, page_size, width)``.
* :func:`gather_pages` / :func:`append_token` / :func:`seed_pages` —
  the jittable fixed-shape device primitives the XLA paged decode
  path (``attention_decode_paged`` / ``mla_decode_paged``) is built
  from; the Pallas decode kernel (:mod:`repro.kernels.paged_attention`)
  reads live pages itself.  Holes (:data:`HOLE`) in the page table are
  clamped on gather (the garbage rows land beyond every sequence's
  valid prefix, where the attention mask kills them) and routed out of
  bounds on scatter (dropped, never corrupting a live page).

Sharding: pools carry no batch dim — the page dim takes the
data-parallel axes and the head/width dim the model axis, both on the
same array (2D), via :func:`repro.dist.sharding.paged_spec`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = [
    "HOLE", "PagedKV", "PagedLatent", "init_paged_kv", "init_paged_latent",
    "gather_pages", "append_token", "seed_pages",
]

#: page-table entry marking an unallocated slot
HOLE = -1


@dataclasses.dataclass
class PagedKV:
    """One attention layer's page pool — the paged
    :class:`~repro.models.layers.KVCache`.  ``k``/``v``:
    ``(n_pages, page_size, n_kv_heads * head_dim)``: a token's KV heads
    side by side in one row, so a page is one dense, lane-aligned block
    on a TPU whatever the head width (a ``head_dim`` of 64 as its own
    minor dim would be padded to 128 lanes, which the Pallas decode
    kernel's page DMAs cannot slice)."""

    k: jax.Array
    v: jax.Array

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]


@dataclasses.dataclass
class PagedLatent:
    """One MLA layer's page pool — the paged
    :class:`~repro.models.mla.MLACache`.  ``c_kv``:
    ``(n_pages, page_size, kv_lora_rank)``, ``k_rope``:
    ``(n_pages, page_size, qk_rope_dim)``."""

    c_kv: jax.Array
    k_rope: jax.Array

    @property
    def page_size(self) -> int:
        return self.c_kv.shape[-2]


jax.tree_util.register_dataclass(PagedKV, data_fields=["k", "v"],
                                 meta_fields=[])
jax.tree_util.register_dataclass(PagedLatent,
                                 data_fields=["c_kv", "k_rope"],
                                 meta_fields=[])


def init_paged_kv(n_pages: int, page_size: int, n_kv_heads: int,
                  head_dim: int, dtype: jnp.dtype) -> PagedKV:
    shape = (n_pages, page_size, n_kv_heads * head_dim)
    return PagedKV(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_paged_latent(n_pages: int, page_size: int, kv_lora_rank: int,
                      qk_rope_dim: int, dtype: jnp.dtype) -> PagedLatent:
    return PagedLatent(
        jnp.zeros((n_pages, page_size, kv_lora_rank), dtype),
        jnp.zeros((n_pages, page_size, qk_rope_dim), dtype))


# ---------------------------------------------------------------------------
# Jittable device primitives
# ---------------------------------------------------------------------------

def gather_pages(pages: jax.Array, table: jax.Array,
                 layer: jax.Array | None = None) -> jax.Array:
    """Page-table gather: ``(P, page, ...)`` pool + ``(B, T)`` table ->
    a contiguous per-sequence ``(B, T*page, ...)`` view.

    With ``layer``, ``pages`` is a stack of per-layer pools
    ``(L, P, page, ...)`` and the view is read from ``pages[layer]`` in
    the same single gather, so the layer's pool is never sliced out
    whole.

    Holes (:data:`HOLE`) clamp to page 0; whatever that page holds
    lands at token slots at/after the sequence's allocated prefix,
    where the downstream ``kv_ids <= pos`` attention mask zeroes it —
    the gathered view needs no select, and the XLA decode path built on
    it stays bitwise equal to the fixed-batch path (the Pallas decode
    kernel agrees with both to f32 rounding only).  The gather reads
    the whole capped span of every slot, live or not."""
    b, t = table.shape
    pool_shape = pages.shape if layer is None else pages.shape[1:]
    ids = jnp.clip(table, 0, pool_shape[0] - 1)
    gathered = (jnp.take(pages, ids, axis=0) if layer is None
                else pages[layer, ids])
    return gathered.reshape((b, t * pool_shape[1]) + pool_shape[2:])


def append_token(pages: jax.Array, table: jax.Array, pos: jax.Array,
                 new: jax.Array, active: jax.Array,
                 layer: jax.Array | None = None) -> jax.Array:
    """Write one token per sequence: ``new[b]`` lands at physical slot
    ``(table[b, pos[b] // page], pos[b] % page)``.

    With ``layer``, ``pages`` is a stack of per-layer pools
    ``(L, P, page, ...)`` and the rows land in ``pages[layer]``: one
    scatter into the stack, which XLA updates in place when the stack
    is loop-carried state.

    Inactive slots (and holes) are routed to page id ``n_pages`` —
    out of bounds, so the scatter drops them (``mode="drop"``) instead
    of corrupting page 0.  Live sequences own disjoint pages, so the
    per-``b`` scatter indices never collide.
    """
    n_pages, page = (pages.shape if layer is None else pages.shape[1:])[:2]
    cap = table.shape[1] * page
    idx = jnp.clip(pos, 0, cap - 1)
    page_ix = jnp.take_along_axis(table, (idx // page)[:, None],
                                  axis=1)[:, 0]
    ok = active & (page_ix >= 0)
    page_ix = jnp.where(ok, page_ix, n_pages)
    if layer is None:
        return pages.at[page_ix, idx % page].set(new, mode="drop")
    return pages.at[layer, page_ix, idx % page].set(new, mode="drop")


def seed_pages(pages: jax.Array, page_ids: jax.Array,
               values: jax.Array) -> jax.Array:
    """Bulk-write a prompt's cache rows into freshly allocated pages.

    ``values``: ``(n * page, ...)`` contiguous token rows (pad to a
    page multiple first), scattered as ``n`` whole pages at
    ``page_ids``; each row is flattened to the pool's row width."""
    n = page_ids.shape[0]
    page = pages.shape[1]
    vals = values.reshape((n, page) + pages.shape[2:])
    return pages.at[page_ids].set(vals)
