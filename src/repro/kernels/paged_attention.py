"""Paged single-token decode attention as a Pallas TPU kernel.

The continuous-batching decode step attends each slot's one new query
token to the slot's cached keys and values. They live in a shared page
pool ``(n_pages, page_size, n_kv_heads * head_dim)`` (one token's KV
heads side by side in one row, :mod:`repro.models.paged_cache`), addressed
through a per-slot page table. The kernel takes a stack of such pools,
one per layer of the model's layer scan, and the index of the layer to
read, so the scan hands it the stack it carries and no layer's pool is
ever sliced out whole. The kernel reads only what is live:

* the per-slot lengths, the page table and the layer are
  scalar-prefetched; for slot ``b`` the kernel copies the pages
  ``0 .. ceil(len[b] / page) - 1`` of its table row straight from the
  layer's pool in HBM, one whole page (every KV head) per DMA, and
  nothing past them: holes and pages beyond the live prefix are never
  read, and an inactive slot (``len == 0``) reads nothing;
* pages are copied in blocks of :func:`_pages_per_block`,
  double-buffered: while one block is reduced the next is in flight,
  also across slots (the grid runs the slots in order, and the last
  block of one slot starts the copy of the next active slot's first
  block), so the grid has one step per slot whatever the capped span;
* query heads are grouped per KV head without repeating anything: query
  head ``c`` is laid out block-diagonally, in the lanes of its KV head
  ``c // G`` (G = query heads per KV head) and zero elsewhere, so one
  MXU product of the ``(H, Hkv * Dh)`` queries with a block's
  ``(tokens, Hkv * Dh)`` keys scores every head against its own KV head,
  and each K/V row is loaded once for all G heads of its group. The
  weighted sum comes out in the same layout, and a last product with a
  stacked identity takes each head's lanes. MHA is the case G = 1.

Arithmetic is the XLA path's: K and V are converted to f32 in VMEM,
scores, the online softmax and the weighted sum run in f32, and the
output is cast to the query's dtype. The softmax is accumulated block by
block, so the order of the sums differs and the two paths agree to f32
rounding, not bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention_pallas"]

_NEG_INF = -1e30

#: bytes of one K (or V) block the kernel aims to copy per loop step:
#: large enough that a block's DMAs amortise the step's fixed cost,
#: small enough that its f32 copies stay a few MB of VMEM
_BLOCK_BYTES = 256 * 1024


def _pages_per_block(page_size: int, row_width: int, itemsize: int,
                    table_pages: int) -> int:
    """Pages copied per double-buffered block: about ``_BLOCK_BYTES`` of
    K, never more than a table row holds."""
    page_bytes = page_size * row_width * itemsize
    return max(1, min(table_pages, _BLOCK_BYTES // page_bytes))


def _paged_decode_kernel(lens_ref, table_ref, nxt_ref,     # scalar prefetch
                         layer_ref,
                         q_ref, diag_ref, take_ref,        # VMEM inputs
                         k_hbm, v_hbm,                     # pools, in HBM
                         o_ref,                            # output
                         kbuf, vbuf, sems, cur_ref,        # scratch
                         acc_ref, m_ref, l_ref, *,
                         n_slots: int, table_pages: int, ppb: int,
                         sm_scale: float):
    b = pl.program_id(0)
    _, n_pages, page, width = k_hbm.shape
    layer = layer_ref[0]
    bk = ppb * page                                  # tokens per block

    def copies(slot, blk, buf):
        """The page DMAs of one block, each with its liveness."""
        live = (lens_ref[slot] + page - 1) // page
        out = []
        for i in range(ppb):
            j = blk * ppb + i

            def make(i=i, j=j):
                pid = jnp.clip(table_ref[slot * table_pages + j], 0,
                               n_pages - 1)
                return (pltpu.make_async_copy(k_hbm.at[layer, pid],
                                              kbuf.at[buf, i],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[layer, pid],
                                              vbuf.at[buf, i],
                                              sems.at[1, buf]))
            out.append((j < live, make))
        return out

    def start(slot, blk, buf):
        for live, make in copies(slot, blk, buf):
            @pl.when(live)
            def _():
                for c in make():
                    c.start()

    def wait(slot, blk, buf):
        for live, make in copies(slot, blk, buf):
            @pl.when(live)
            def _():
                for c in make():
                    c.wait()

    @pl.when(b == 0)
    def _first():
        cur_ref[0] = 0

        @pl.when(nxt_ref[0] < n_slots)
        def _():
            start(nxt_ref[0], 0, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    n_blocks = (length + bk - 1) // bk
    q = q_ref[0].astype(jnp.float32)                    # (H, Hkv * Dh)
    heads = q.shape[0]

    def body(blk, carry):
        buf = cur_ref[0]
        more = blk + 1 < n_blocks
        nslot = jnp.where(more, b, nxt_ref[b + 1])

        @pl.when(nslot < n_slots)
        def _():
            start(nslot, jnp.where(more, blk + 1, 0), 1 - buf)

        wait(b, blk, buf)
        left = length - blk * bk                 # live tokens from here
        k = kbuf[buf].astype(jnp.float32).reshape(bk, width)
        v = vbuf[buf].astype(jnp.float32).reshape(bk, width)
        # rows past the live pages hold whatever VMEM held: their scores
        # are masked, and their values zeroed so a zero weight never
        # meets a NaN
        row = jax.lax.broadcasted_iota(jnp.int32, (bk, width), 0)
        v = jnp.where(row < left, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (H, bk)
        tok = jax.lax.broadcasted_iota(jnp.int32, (heads, bk), 1)
        s = jnp.where(tok < left, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        cur_ref[0] = 1 - buf
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    # each head's own KV head's lanes: (H, Hkv * Dh) -> (H, Dh); an
    # inactive slot ran no block, and 0 / guarded 0 is a finite zero
    out = jnp.dot(acc_ref[...] * diag_ref[...], take_ref[...],
                  preferred_element_type=jnp.float32)
    o_ref[0] = (out / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, lengths: jax.Array,
                                  page_table: jax.Array,
                                  layer: int | jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """softmax(q kᵀ / sqrt(Dh)) v for one query token per slot, over the
    slot's live pages in the pools of ``layer``.

    q: ``(B, H, Dh)``; k_pages / v_pages: ``(L, n_pages, page, Hkv *
    Dh)``, a stack of per-layer pools (a lone pool is passed as
    ``pool[None]``), with ``H`` a multiple of ``Hkv``; lengths: ``(B,)``
    live tokens per slot (0 = inactive slot, whose output is zeros);
    page_table: ``(B, T)`` physical page of each logical page (entries
    past ``ceil(len / page)`` are never read); layer: the index into
    ``L`` to read, an int or an int32 scalar. Returns ``(B, H, Dh)`` in
    q's dtype.
    """
    b, heads, dh = q.shape
    _, n_pages, page, width = k_pages.shape
    if k_pages.shape != v_pages.shape or width % dh \
            or heads % (width // dh):
        raise ValueError(f"bad paged attention shapes q {q.shape}, "
                         f"pages {k_pages.shape} / {v_pages.shape}")
    kvh = width // dh
    table_pages = page_table.shape[1]
    ppb = _pages_per_block(page, width, k_pages.dtype.itemsize, table_pages)
    # diag[c, j]: lane j holds query head c's KV head (c // G);
    # take[j, d]: lane j is lane d of its head
    diag = np.kron(np.repeat(np.eye(kvh), heads // kvh, axis=0),
                   np.ones((1, dh)))
    take = np.tile(np.eye(dh), (kvh, 1))
    q_diag = (q[:, :, None, :]
              * jnp.asarray(diag.reshape(heads, kvh, dh), q.dtype)
              ).reshape(b, heads, width)
    lengths = lengths.astype(jnp.int32)
    # nxt[0]: the first active slot; nxt[s + 1]: the next active slot
    # after s (B when there is none)
    ids = jnp.where(lengths > 0, jnp.arange(b, dtype=jnp.int32), b)
    nxt = jnp.concatenate([jax.lax.cummin(ids[::-1])[::-1],
                           jnp.full((1,), b, jnp.int32)])

    kernel = functools.partial(
        _paged_decode_kernel, n_slots=b, table_pages=table_pages, ppb=ppb,
        sm_scale=float(dh) ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((heads, width), lambda i, *_: (0, 0)),
            pl.BlockSpec((width, dh), lambda i, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, dh), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, width), k_pages.dtype),
            pltpu.VMEM((2, ppb, page, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, width), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths, page_table.reshape(-1).astype(jnp.int32), nxt,
      jnp.asarray(layer, jnp.int32).reshape(1), q_diag,
      jnp.asarray(diag, jnp.float32), jnp.asarray(take, jnp.float32),
      k_pages, v_pages)
