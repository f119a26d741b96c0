"""Blocked (flash) attention Pallas kernels with online softmax.

Used by the prefill path of every attention architecture (32k-token
shapes make materialising the (S, S) score matrix impossible: 32768² x
4 B = 4 GB per head).  Supports causal masking and an optional sliding
window (mixtral SWA, recurrentgemma local attention).

Two KV-grid variants, selected by ``grid=`` (the tuner's
``GemmConfig.flash_grid`` knob — see :mod:`repro.core.costmodel`):

* ``dense`` — grid ``(BH, gq, gkv)``.  Fully-masked tiles are skipped
  with ``pl.when`` (no MXU work), but every grid step still *launches*
  and every K/V block is still streamed HBM->VMEM — neither memory
  traffic nor step count reflects the causal triangle.
* ``tri`` — block-sparse triangular grid.  A host-built tile map
  (:func:`flash_tile_map`, fed through scalar prefetch) bounds the
  sequential KV axis per Q block row (and per window band), so
  above-diagonal tiles are never launched and their K/V blocks never
  copied — roughly halving both launches and K/V HBM traffic on causal
  prefill.  Bit-compatible with the dense grid (identical block
  arithmetic in the same order; only the skipped all-masked tiles —
  which contribute exactly nothing — differ).

TPU adaptation: the KV sequence axis is a *sequential* grid dimension
with running (max, denominator, accumulator) carried in fp32 VMEM
scratch — the memory-hierarchy translation of the GPU warp-level
online-softmax.  The sequential-axis Pallas pipeline double-buffers the
K/V block fetches automatically; the triangular map keeps tiles in
row-major order so each Q row's K/V stream stays contiguous for that
pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "flash_tile_map", "flash_grid_counts"]

_NEG_INF = -1e30

FLASH_GRID_KINDS = ("dense", "tri")


def _clamp_blocks(sq: int, skv: int, bq: int, bkv: int) -> tuple[int, int]:
    """The effective (bq, bkv) the kernels run: never larger than the
    (sublane-padded) sequence extents."""
    return min(bq, max(8, sq)), min(bkv, max(8, skv))


def flash_tile_map(sq: int, skv: int, bq: int, bkv: int, *,
                   causal: bool = True, window: int | None = None
                   ) -> tuple[np.ndarray, np.ndarray,
                              np.ndarray, np.ndarray]:
    """Block-sparse tile list for the triangular/banded flash grid.

    Returns ``(qt, kvt, first, last)`` int32 arrays, one entry per
    launched tile, in row-major (Q row outer, KV ascending) order:
    ``qt[t]``/``kvt[t]`` are the block indices the sequential grid step
    ``t`` loads, ``first[t]``/``last[t]`` flag the row's scratch init /
    output write.  Per Q row ``i`` (blocks over the *padded* Sq so every
    output row is written):

    * causal bounds the KV axis above at the diagonal,
      ``hi = min(gkv-1, (i*bq + bq - 1) // bkv)`` — tiles past it are
      fully masked and never emitted;
    * a sliding window bounds it below at the band edge,
      ``lo = max(0, (i*bq - window + 1) // bkv)``;
    * the KV-length bound caps ``hi`` at the last block holding a real
      (< skv) key, so fully-padded KV tiles are never emitted either.

    A row whose band is empty (window entirely in the future relative
    to every key) degenerates to one flagged-first-and-last tile whose
    body the kernel masks out entirely — init + finish still run, so
    the row's output is written (as zeros, matching the dense grid).
    """
    gq = -(-sq // bq)
    gkv = -(-skv // bkv)
    kv_hi = (skv - 1) // bkv          # last block with a real key
    qt, kvt, first, last = [], [], [], []
    for i in range(gq):
        hi = kv_hi
        if causal:
            hi = min(hi, (i * bq + bq - 1) // bkv)
        lo = 0
        if window is not None:
            lo = max(0, (i * bq - window + 1) // bkv)
        if lo > hi:                   # fully-masked row: degenerate tile
            lo = hi = min(lo, gkv - 1)
        for j in range(lo, hi + 1):
            qt.append(i)
            kvt.append(j)
            first.append(1 if j == lo else 0)
            last.append(1 if j == hi else 0)
    return (np.asarray(qt, np.int32), np.asarray(kvt, np.int32),
            np.asarray(first, np.int32), np.asarray(last, np.int32))


def flash_grid_counts(sq: int, skv: int, bq: int, bkv: int, *,
                      causal: bool = True, window: int | None = None
                      ) -> tuple[int, int]:
    """(triangular grid steps, dense grid steps) per batch-head, after
    the same block clamping :func:`flash_attention_pallas` applies —
    the launch saving the cost model prices and bench_flash measures."""
    bq_, bkv_ = _clamp_blocks(sq, skv, bq, bkv)
    gq, gkv = -(-sq // bq_), -(-skv // bkv_)
    qt, _, _, _ = flash_tile_map(sq, skv, bq_, bkv_,
                                 causal=causal, window=window)
    return len(qt), gq * gkv


def _block_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                  q_start, kv_start, bq: int, bkv: int, skv: int,
                  causal: bool, window: int | None,
                  sm_scale: float) -> None:
    """One online-softmax block step, shared by both grid variants."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale       # (bq, bkv)

    q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # the KV-length mask is unconditional: padded key columns hold zero
    # vectors whose score (0 * sm_scale = 0) would otherwise leak into
    # the denominator whenever causality alone doesn't hide them (any
    # q id >= skv, i.e. every causal sq > skv call)
    mask = kv_ids < skv
    if causal:
        mask &= kv_ids <= q_ids
    if window is not None:
        mask &= kv_ids > q_ids - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]                                    # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                   # (bq, bkv)
    corr = jnp.exp(m_prev - m_new)                           # (bq, 1)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _visible(q_start, kv_start, *, bq: int, bkv: int, skv: int,
             padded: bool, causal: bool, window: int | None):
    """Does this tile intersect the mask at all?  Invisible tiles are
    skipped whole: no MXU work on the dense grid, and — crucially — no
    uniform-p garbage from an all-``_NEG_INF`` score block (exp(0)=1)
    before a row's running max is seeded."""
    visible = jnp.bool_(True)
    if padded:
        visible &= kv_start < skv
    if causal:
        visible &= kv_start <= q_start + bq - 1
    if window is not None:
        visible &= kv_start + bkv - 1 > q_start - window
    return visible


def _flash_dense_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                        *, n_kv: int, bq: int, bkv: int, skv: int,
                        causal: bool, window: int | None, sm_scale: float):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    kv_start = ikv * bkv
    body = functools.partial(
        _block_update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
        q_start=q_start, kv_start=kv_start, bq=bq, bkv=bkv, skv=skv,
        causal=causal, window=window, sm_scale=sm_scale)

    padded = n_kv * bkv != skv
    if causal or window is not None or padded:
        pl.when(_visible(q_start, kv_start, bq=bq, bkv=bkv, skv=skv,
                         padded=padded, causal=causal,
                         window=window))(body)
    else:
        body()

    @pl.when(ikv == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _flash_tri_kernel(qt_ref, kvt_ref, firstf_ref, lastf_ref,
                      q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                      *, bq: int, bkv: int, skv: int, causal: bool,
                      window: int | None, sm_scale: float):
    """Block-sparse variant: grid (BH, T) over the prefetched tile map.
    The scalar-prefetch refs also drive the BlockSpec index maps, so a
    tile absent from the map is neither launched nor DMA'd."""
    t = pl.program_id(1)
    iq = qt_ref[t]
    ikv = kvt_ref[t]

    @pl.when(firstf_ref[t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    kv_start = ikv * bkv
    body = functools.partial(
        _block_update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
        q_start=q_start, kv_start=kv_start, bq=bq, bkv=bkv, skv=skv,
        causal=causal, window=window, sm_scale=sm_scale)
    # emitted tiles are visible by construction except a fully-masked
    # row's degenerate placeholder (and padded-KV straddle columns are
    # handled by the in-block mask) — the guard keeps those exact
    pl.when(_visible(q_start, kv_start, bq=bq, bkv=bkv, skv=skv,
                     padded=True, causal=causal, window=window))(body)

    @pl.when(lastf_ref[t] == 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "causal", "window",
                                    "sm_scale", "interpret", "grid"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           bq: int = 512, bkv: int = 512,
                           causal: bool = True, window: int | None = None,
                           sm_scale: float | None = None,
                           interpret: bool = False,
                           grid: str = "dense") -> jax.Array:
    """softmax(q kᵀ / sqrt(D), causal/windowed) v  over (BH, S, D) inputs.

    q: (BH, Sq, D), k/v: (BH, Skv, D) — callers fold batch x heads into
    the leading dim (and broadcast KV heads for GQA).  Sq/Skv are padded
    to the block grid; padded KV columns are masked out explicitly (the
    KV-length mask), so ragged causal *and* non-causal shapes are exact.

    ``grid`` picks the KV grid (see module docstring): ``"dense"`` or
    ``"tri"`` (block-sparse triangular/banded — identical output, fewer
    launched tiles whenever causality or a window masks whole blocks).
    """
    if q.ndim != 3 or k.shape != v.shape or q.shape[0] != k.shape[0] \
            or q.shape[2] != k.shape[2]:
        raise ValueError(f"bad attention shapes {q.shape} {k.shape}")
    if grid not in FLASH_GRID_KINDS:
        raise ValueError(f"unknown flash grid {grid!r}; "
                         f"expected one of {FLASH_GRID_KINDS}")
    bh, sq, d = q.shape
    skv = k.shape[1]
    sm_scale = sm_scale if sm_scale is not None else float(d) ** -0.5

    bq_, bkv_ = _clamp_blocks(sq, skv, bq, bkv)
    gq, gkv = pl.cdiv(sq, bq_), pl.cdiv(skv, bkv_)
    qp = jnp.pad(q, ((0, 0), (0, gq * bq_ - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, gkv * bkv_ - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, gkv * bkv_ - skv), (0, 0)))
    scratch = [
        pltpu.VMEM((bq_, d), jnp.float32),
        pltpu.VMEM((bq_, 128), jnp.float32),
        pltpu.VMEM((bq_, 128), jnp.float32),
    ]

    if grid == "tri":
        qt, kvt, first, last = flash_tile_map(
            sq, skv, bq_, bkv_, causal=causal, window=window)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bh, len(qt)),
            in_specs=[
                pl.BlockSpec((1, bq_, d),
                             lambda b, t, qt, kvt, ff, lf: (b, qt[t], 0)),
                pl.BlockSpec((1, bkv_, d),
                             lambda b, t, qt, kvt, ff, lf: (b, kvt[t], 0)),
                pl.BlockSpec((1, bkv_, d),
                             lambda b, t, qt, kvt, ff, lf: (b, kvt[t], 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bq_, d), lambda b, t, qt, kvt, ff, lf: (b, qt[t], 0)),
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            functools.partial(_flash_tri_kernel, bq=bq_, bkv=bkv_,
                              skv=skv, causal=causal, window=window,
                              sm_scale=sm_scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bh, gq * bq_, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(qt), jnp.asarray(kvt), jnp.asarray(first),
          jnp.asarray(last), qp, kp, vp)
        return out[:, :sq, :]

    out = pl.pallas_call(
        functools.partial(_flash_dense_kernel, n_kv=gkv, bq=bq_, bkv=bkv_,
                          skv=skv, causal=causal, window=window,
                          sm_scale=sm_scale),
        grid=(bh, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv_, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv_, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, gq * bq_, d), q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq, :]
