"""Paged KV cache: host-side page allocator + device-side page pools.

The fixed-batch serving cache allocates ``B x cache_len`` token slots up
front, so per-chip cache memory caps the batch at
``B_max = mem / (cache_len * bytes_per_token)`` even when most requests
are far shorter than ``cache_len``.  Paging (vLLM, arXiv 2309.06180)
breaks the cache into fixed-size **pages** of ``page_size`` token slots
handed out from a free list; each sequence holds exactly the pages its
actual length needs, and a **page table** maps its logical token
positions to physical pages.  The ceiling becomes total *tokens in
flight*, not batch size — the property the continuous-batching
scheduler (:mod:`repro.serve.scheduler`) is built on.

Split of responsibilities:

* :class:`PageAllocator` — pure-Python free-list bookkeeping (admit /
  grow / retire), no jax.  Its invariants (no double-allocation,
  free + live conservation, clean failure on exhaustion) are the
  property-tested contract (tests/test_kv_cache_property.py).
* :class:`PagedKV` / :class:`PagedLatent` — registered pytrees holding
  one attention layer's page pool: ``(n_pages, page_size, width)``
  arrays, the direct paged analogue of
  :class:`~repro.models.layers.KVCache` and
  :class:`~repro.models.mla.MLACache`.
* :func:`gather_pages` / :func:`append_token` / :func:`seed_pages` —
  the jittable fixed-shape device primitives the XLA paged decode
  path (``attention_decode_paged`` / ``mla_decode_paged``) is built
  from; the Pallas decode kernel (:mod:`repro.kernels.paged_attention`)
  reads live pages itself.  Holes in the page table are clamped on
  gather (the garbage rows land beyond every sequence's valid prefix,
  where the attention mask kills them) and routed out of bounds on
  scatter (dropped, never corrupting a live page).

Sharding: pools carry no batch dim — the page dim takes the
data-parallel axes and the head/width dim the model axis, both on the
same array (2D), via :func:`repro.dist.sharding.paged_spec`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = [
    "HOLE", "PageAllocator", "PagedKV", "PagedLatent",
    "gather_pages", "append_token", "seed_pages", "pages_for",
]

#: page-table entry marking an unallocated slot
HOLE = -1


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` token slots (ceil division)."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens={n_tokens} < 0")
    return -(-n_tokens // page_size)


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list page allocator with per-sequence page accounting.

    All-or-nothing: :meth:`admit` and :meth:`grow` either return the
    full list of newly allocated page ids or ``None`` with the
    allocator state untouched — a caller that cannot get its pages
    defers (re-queues the request), it never observes a half-allocated
    sequence.  :meth:`retire` frees exactly the sequence's pages.

    The invariants the property suite pins:

    * a page is never handed out twice while live;
    * ``free_pages + live_pages == n_pages`` after every operation;
    * retiring a sequence frees exactly the page count it held;
    * exhaustion returns ``None`` and changes nothing.
    """

    def __init__(self, n_pages: int, page_size: int) -> None:
        if n_pages <= 0:
            raise ValueError(f"n_pages={n_pages} <= 0")
        if page_size <= 0:
            raise ValueError(f"page_size={page_size} <= 0")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # LIFO free stack: recently retired pages are re-used first,
        # keeping the hot pool compact
        self._free: list[int] = list(range(n_pages - 1, -1, -1))
        self._live: dict[int, list[int]] = {}

    # -- views ----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return sum(len(p) for p in self._live.values())

    @property
    def live_seqs(self) -> tuple[int, ...]:
        return tuple(self._live)

    def pages_of(self, seq_id: int) -> list[int]:
        """The sequence's pages in logical order (copy)."""
        return list(self._live[seq_id])

    def can_admit(self, n_tokens: int) -> bool:
        return pages_for(n_tokens, self.page_size) <= len(self._free)

    # -- mutations ------------------------------------------------------
    def admit(self, seq_id: int, n_tokens: int) -> list[int] | None:
        """Allocate pages for a new sequence of ``n_tokens`` slots.

        Returns the page ids (logical order) or ``None`` when the pool
        cannot cover the request — admission deferred, nothing changed.
        """
        if seq_id in self._live:
            raise ValueError(f"seq {seq_id} already live")
        need = pages_for(n_tokens, self.page_size)
        if need == 0:
            raise ValueError(f"admit of empty sequence {seq_id}")
        if need > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(need)]
        self._live[seq_id] = pages
        return pages

    def grow(self, seq_id: int, n_tokens_total: int) -> list[int] | None:
        """Extend a live sequence to ``n_tokens_total`` slots.

        Returns the *newly* allocated page ids ([] when already
        covered) or ``None`` when the pool is exhausted — the sequence
        keeps its current pages, nothing is partially allocated.
        """
        held = self._live[seq_id]
        need = pages_for(n_tokens_total, self.page_size) - len(held)
        if need <= 0:
            return []
        if need > len(self._free):
            return None
        fresh = [self._free.pop() for _ in range(need)]
        held.extend(fresh)
        return fresh

    def retire(self, seq_id: int) -> int:
        """Free a live sequence's pages; returns how many were freed."""
        pages = self._live.pop(seq_id)
        self._free.extend(pages)
        return len(pages)

    def check(self) -> None:
        """Raise AssertionError when any allocator invariant is broken."""
        live = [p for pages in self._live.values() for p in pages]
        assert len(set(live)) == len(live), "double-allocated live page"
        assert not set(live) & set(self._free), "live page on free list"
        assert len(live) + len(self._free) == self.n_pages, \
            "page conservation violated"
        assert all(0 <= p < self.n_pages for p in live + self._free)


# ---------------------------------------------------------------------------
# Device-side pools
# ---------------------------------------------------------------------------

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
