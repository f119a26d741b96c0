"""Property tests for the paged-KV page allocator.

The allocator contract the continuous-batching scheduler leans on:

* a live page is never handed out twice;
* ``free + live == n_pages`` after *every* operation;
* retiring a sequence frees exactly the page count it held;
* exhaustion defers cleanly — ``None`` returned, state untouched.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.kv_cache import PageAllocator, pages_for


def test_pages_for_ceil():
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2
    assert pages_for(0, 4) == 0
    with pytest.raises(ValueError):
        pages_for(-1, 4)


# ---------------------------------------------------------------------------
# arbitrary admit/grow/retire trajectories keep every invariant
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n_pages=st.integers(1, 24), page_size=st.integers(1, 8),
       seed=st.integers(0, 10_000), n_ops=st.integers(1, 120))
def test_trajectory_invariants(n_pages, page_size, seed, n_ops):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(n_pages, page_size)
    next_seq = 0
    held: dict[int, int] = {}          # seq -> pages it must hold
    for _ in range(n_ops):
        op = rng.choice(["admit", "grow", "retire"])
        if op == "admit":
            want = int(rng.integers(1, 3 * page_size + 1))
            got = alloc.admit(next_seq, want)
            need = pages_for(want, page_size)
            if need > n_pages - sum(held.values()):
                assert got is None      # exhaustion defers, no change
            else:
                assert got is not None and len(got) == need
                assert len(set(got)) == need
                held[next_seq] = need
                next_seq += 1
        elif op == "grow" and held:
            seq = int(rng.choice(list(held)))
            total = int(rng.integers(1, 5 * page_size + 1))
            before = alloc.pages_of(seq)
            got = alloc.grow(seq, total)
            need = pages_for(total, page_size) - len(before)
            if need <= 0:
                assert got == []        # already covered
            elif need > n_pages - sum(held.values()):
                assert got is None
                assert alloc.pages_of(seq) == before   # untouched
            else:
                assert len(got) == need
                assert alloc.pages_of(seq) == before + got
                held[seq] += need
        elif op == "retire" and held:
            seq = int(rng.choice(list(held)))
            assert alloc.retire(seq) == held.pop(seq)
        # the conservation / no-double-allocation audit after every op
        alloc.check()
        assert alloc.free_pages + alloc.live_pages == n_pages
        assert alloc.live_pages == sum(held.values())
    # live pages across sequences are pairwise disjoint
    all_pages = [p for s in alloc.live_seqs for p in alloc.pages_of(s)]
    assert len(set(all_pages)) == len(all_pages)


@settings(max_examples=15, deadline=None)
@given(page_size=st.integers(1, 8), n_seqs=st.integers(1, 6))
def test_retire_frees_exactly_and_pages_recycle(page_size, n_seqs):
    alloc = PageAllocator(n_seqs * 3, page_size)
    admitted = {}
    for s in range(n_seqs):
        admitted[s] = alloc.admit(s, (s % 3 + 1) * page_size)
        assert admitted[s] is not None
    for s in range(n_seqs):
        assert alloc.retire(s) == len(admitted[s])
        alloc.check()
    assert alloc.free_pages == n_seqs * 3
    # every freed page is allocatable again
    again = alloc.admit(99, n_seqs * 3 * page_size)
    assert again is not None and sorted(again) == list(range(n_seqs * 3))


def test_exhaustion_defers_without_corruption():
    alloc = PageAllocator(4, 2)
    a = alloc.admit(0, 6)               # 3 pages
    assert len(a) == 3
    assert alloc.admit(1, 4) is None    # needs 2, only 1 free
    alloc.check()
    assert alloc.free_pages == 1
    assert alloc.pages_of(0) == a       # survivor untouched
    b = alloc.admit(1, 2)               # 1 page fits
    assert len(b) == 1 and not set(b) & set(a)
    assert alloc.grow(0, 8) is None     # 4th page: pool dry
    assert alloc.pages_of(0) == a
    alloc.retire(1)
    assert alloc.grow(0, 8) == b        # freed page recycles (LIFO)


def test_allocator_rejects_bad_usage():
    alloc = PageAllocator(4, 2)
    with pytest.raises(ValueError):
        alloc.admit(0, 0)               # empty sequence
    alloc.admit(0, 2)
    with pytest.raises(ValueError):
        alloc.admit(0, 2)               # duplicate seq id
    with pytest.raises(KeyError):
        alloc.retire(7)                 # never admitted
    with pytest.raises(ValueError):
        PageAllocator(0, 2)
    with pytest.raises(ValueError):
        PageAllocator(4, 0)
