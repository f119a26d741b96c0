"""Paged KV cache, host side: the page allocator.

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

:class:`PageAllocator` is pure-Python free-list bookkeeping (admit /
grow / retire), no jax.  Its invariants (no double-allocation,
free + live conservation, clean failure on exhaustion) are the
property-tested contract (tests/test_kv_cache_property.py).  How a
cache lives in the pages on the device — the pools, the hole sentinel
and the gather/append/seed primitives — is
:mod:`repro.models.paged_cache`.
"""

from __future__ import annotations

__all__ = ["PageAllocator", "pages_for"]


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` token slots (ceil division)."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens={n_tokens} < 0")
    return -(-n_tokens // page_size)


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
