"""Tiled Pallas TPU matmul with run-time-selectable BlockSpec tiling.

This is the compute object ADSALA tunes: the (bm, bk, bn) tile triple is
one axis of the tuner's worker configuration (DESIGN.md §Hardware
adaptation — the TPU analogue of the paper's cache-blocking interaction
with thread count).  The kernel accumulates in fp32 VMEM scratch over a
sequential K grid dimension; M and N grid dimensions are parallel.

Layout notes (TPU):
  * block shapes should be multiples of (8, 128) for f32 / (16, 128) for
    bf16; DEFAULT_TILES in core.costmodel respects this,
  * the fp32 accumulator lives in VMEM scratch and is flushed to the
    output block on the last K step,
  * dimension_semantics marks K "arbitrary" so Mosaic keeps revisits of
    the same (i, j) output block in order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_pallas"]


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "interpret",
                                    "out_dtype"))
def matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                  bk: int = 128, bn: int = 128, interpret: bool = False,
                  out_dtype: jnp.dtype | None = None) -> jax.Array:
    """C[m, n] = A[m, k] @ B[k, n] with explicit VMEM tiling.

    Operands with dimensions not divisible by the tile are zero-padded to
    the tile grid and the result sliced back — zero rows/columns do not
    perturb the product.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad GEMM shapes {a.shape} x {b.shape}")
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype

    gm, gk, gn = pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(n, bn)
    a = _pad_to(a, gm * bm, gk * bk)
    b = _pad_to(b, gk * bk, gn * bn)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
