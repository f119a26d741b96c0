"""Grouped (expert-batched) Pallas matmul for MoE layers.

Computes Y[e] = X[e] @ W[e] for every expert e over fixed-capacity
token buckets — the TPU-idiomatic MoE formulation (dense dispatch into
(E, capacity, d) buckets; no dynamic shapes).  The per-expert GEMMs are
exactly the paper's "small and irregular" regime (capacity is usually a
few hundred rows), which is where ADSALA's tuner gives the largest wins;
the tile triple here is tuned with the same worker-configuration model
as the plain matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul_pallas"]


def _grouped_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pad3(x: jax.Array, d1: int, d2: int) -> jax.Array:
    p1, p2 = d1 - x.shape[1], d2 - x.shape[2]
    if p1 or p2:
        x = jnp.pad(x, ((0, 0), (0, p1), (0, p2)))
    return x


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "interpret",
                                    "out_dtype"))
def grouped_matmul_pallas(x: jax.Array, w: jax.Array, *, bm: int = 128,
                          bk: int = 128, bn: int = 128,
                          interpret: bool = False,
                          out_dtype: jnp.dtype | None = None) -> jax.Array:
    """Y[e, c, f] = X[e, c, d] @ W[e, d, f] for all experts e."""
    if x.ndim != 3 or w.ndim != 3 or x.shape[0] != w.shape[0] \
            or x.shape[2] != w.shape[1]:
        raise ValueError(f"bad grouped shapes {x.shape} x {w.shape}")
    e, c, d = x.shape
    _, _, f = w.shape
    out_dtype = out_dtype or x.dtype

    gm, gk, gn = pl.cdiv(c, bm), pl.cdiv(d, bk), pl.cdiv(f, bn)
    x = _pad3(x, gm * bm, gk * bk)
    w = _pad3(w, gk * bk, gn * bn)

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, n_k=gk),
        grid=(e, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, kk: (g, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, kk: (g, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, kk: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, gm * bm, gn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :c, :f]
