"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Queries and keys/values are projected through low-rank latents; the
decode cache stores only the compressed latent c_kv (kv_lora_rank) plus
the decoupled RoPE key (qk_rope_dim) per token — the memory saving that
defines MLA.  Shapes follow the paper: per head the query/key split into
a non-positional part (qk_nope_dim) and a shared rotary part
(qk_rope_dim); values have their own head dim.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import (
    chunked_attention_xla,
    linear,
    rmsnorm,
    rope_angles,
)
from repro.models.paged_cache import append_token, gather_pages
from repro.models.params import ParamDef

__all__ = ["MLASpec", "mla_defs", "mla_train", "mla_decode",
           "mla_decode_paged", "MLACache", "init_mla_cache",
           "seed_mla_cache"]


@dataclasses.dataclass(frozen=True)
class MLASpec:
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def mla_defs(s: MLASpec) -> dict:
    h = s.n_heads
    return {
        # query path: d -> q_lora -> heads * (nope + rope)
        "wq_a": ParamDef((s.d_model, s.q_lora_rank), ("embed", "lora")),
        "q_norm": ParamDef((s.q_lora_rank,), (None,), init="ones"),
        "wq_b": ParamDef((s.q_lora_rank, h * s.qk_head_dim),
                         ("lora", "heads")),
        # kv path: d -> kv_lora (+ shared rope key direct from d)
        "wkv_a": ParamDef((s.d_model, s.kv_lora_rank), ("embed", "lora")),
        "kv_norm": ParamDef((s.kv_lora_rank,), (None,), init="ones"),
        "wk_rope": ParamDef((s.d_model, s.qk_rope_dim), ("embed", None)),
        "wk_b": ParamDef((s.kv_lora_rank, h * s.qk_nope_dim),
                         ("lora", "heads")),
        "wv_b": ParamDef((s.kv_lora_rank, h * s.v_head_dim),
                         ("lora", "heads")),
        "wo": ParamDef((h * s.v_head_dim, s.d_model), ("heads", "embed")),
    }


def _rope_1head(x: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate a (B, S, R) shared rope key / (B, S, H, R) query rope part.

    ``positions`` is (S,) — or (B, S) when every sequence in the batch
    sits at its own position (the paged continuous-batching decode)."""
    r = x.shape[-1]
    sin, cos = rope_angles(positions, r)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    if positions.ndim == 2:   # per-sequence: sin/cos already (B, S, r/2)
        if x.ndim == 4:
            sin = sin[:, :, None, :]
            cos = cos[:, :, None, :]
    elif x.ndim == 4:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:
        sin = sin[None, :, :]
        cos = cos[None, :, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _latents(p: dict, x: jax.Array, s: MLASpec, positions: jax.Array,
             tuner=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q (B,S,H,qk_head_dim), c_kv (B,S,R_kv), k_rope (B,S,R_rope)."""
    b, sq, _ = x.shape
    # latent down-projections (d -> lora rank) are the skinny GEMMs MLA
    # trades cache memory for; tag them so the tuner prices that shape
    ops.observe(b * sq, s.d_model,
                s.q_lora_rank + s.kv_lora_rank + s.qk_rope_dim, tuner,
                site="mla.down_proj")
    ops.observe(b * sq, s.q_lora_rank, s.n_heads * s.qk_head_dim,
                tuner, site="mla.up_proj_q")
    q_lat = rmsnorm(linear(x, p["wq_a"]), p["q_norm"])
    q = linear(q_lat, p["wq_b"]).reshape(b, sq, s.n_heads, s.qk_head_dim)
    q_nope, q_rope = q[..., : s.qk_nope_dim], q[..., s.qk_nope_dim:]
    q_rope = _rope_1head(q_rope, positions)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    c_kv = rmsnorm(linear(x, p["wkv_a"]), p["kv_norm"])
    k_rope = _rope_1head(linear(x, p["wk_rope"]), positions)
    return q, c_kv, k_rope


def _expand_kv(p: dict, c_kv: jax.Array, k_rope: jax.Array, s: MLASpec,
               tuner=None) -> tuple[jax.Array, jax.Array]:
    """Decompress latents to per-head K (nope+rope) and V."""
    b, sk, _ = c_kv.shape
    # latent up-projection (kv lora rank -> per-head K/V)
    ops.observe(b * sk, s.kv_lora_rank,
                s.n_heads * (s.qk_nope_dim + s.v_head_dim), tuner,
                site="mla.up_proj_kv")
    k_nope = linear(c_kv, p["wk_b"]).reshape(b, sk, s.n_heads, s.qk_nope_dim)
    v = linear(c_kv, p["wv_b"]).reshape(b, sk, s.n_heads, s.v_head_dim)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (b, sk, s.n_heads, s.qk_rope_dim))
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    return k, v


def mla_train(p: dict, x: jax.Array, s: MLASpec, tuner=None
              ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (out, (c_kv, k_rope)) — the latents seed the decode cache."""
    b, sq, _ = x.shape
    positions = jnp.arange(sq)
    q, c_kv, k_rope = _latents(p, x, s, positions, tuner)
    k, v = _expand_kv(p, c_kv, k_rope, s, tuner)
    # causal scores: SYRK-shaped like GQA attention (triangular output)
    ops.observe(sq, s.qk_head_dim, sq, tuner, routine="syrk",
                site="mla.qk", count=b * s.n_heads)
    out = chunked_attention_xla(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=None,
        chunk=min(512, sq))
    out = out.transpose(0, 2, 1, 3).reshape(b, sq,
                                            s.n_heads * s.v_head_dim)
    return linear(out, p["wo"]), (c_kv, k_rope)


def seed_mla_cache(c_kv: jax.Array, k_rope: jax.Array,
                   capacity: int) -> MLACache:
    b, sq, _ = c_kv.shape
    pad = capacity - sq
    if pad < 0:
        raise ValueError(f"prompt {sq} exceeds cache {capacity}")
    return MLACache(jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                    jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))))


@dataclasses.dataclass
class MLACache:
    """Compressed latent cache: (B, cap, kv_lora_rank) + (B, cap, rope)."""
    c_kv: jax.Array
    k_rope: jax.Array


jax.tree_util.register_dataclass(
    MLACache, data_fields=["c_kv", "k_rope"], meta_fields=[])


def init_mla_cache(batch: int, capacity: int, s: MLASpec,
                   dtype: jnp.dtype) -> MLACache:
    return MLACache(
        jnp.zeros((batch, capacity, s.kv_lora_rank), dtype),
        jnp.zeros((batch, capacity, s.qk_rope_dim), dtype))


def _absorbed_attend(p: dict, q: jax.Array, c_kv: jax.Array,
                     k_rope: jax.Array, valid: jax.Array,
                     s: MLASpec) -> jax.Array:
    """The XLA MLA decode core shared by the contiguous and the paged
    cache, in latent space at f32.

    q (B, 1, H, nope + rope); c_kv (B, cap, R_kv); k_rope
    (B, cap, rope); ``valid`` (1 or B, cap) marks the cache slots each
    sequence may attend to.  Returns (B, H, v_head_dim) f32.  One copy
    keeps the paged path bitwise equal to the fixed-batch one."""
    q_nope = q[..., : s.qk_nope_dim]       # (B, 1, H, nope)
    q_rope = q[..., s.qk_nope_dim:]        # (B, 1, H, rope)
    # absorb: q_lat[h] = q_nope[h] @ wk_b[h]^T  -> (B, H, R_kv)
    wk_b = p["wk_b"].reshape(s.kv_lora_rank, s.n_heads, s.qk_nope_dim)
    q_lat = jnp.einsum("bohd,rhd->bhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    s_lat = jnp.einsum("bhr,bkr->bhk", q_lat, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bohd,bkd->bhk", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    scores = (s_lat + s_rope) * (s.qk_head_dim ** -0.5)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # output in latent space, then decompress through wv_b per head
    o_lat = jnp.einsum("bhk,bkr->bhr", probs, c_kv.astype(jnp.float32))
    wv_b = p["wv_b"].reshape(s.kv_lora_rank, s.n_heads, s.v_head_dim)
    return jnp.einsum("bhr,rhd->bhd", o_lat, wv_b.astype(jnp.float32))


def mla_decode(p: dict, x: jax.Array, s: MLASpec, cache: MLACache,
               pos: jax.Array, tuner=None) -> tuple[jax.Array, MLACache]:
    """One-token decode against the latent cache.

    Absorbed-projection trick: scores are computed in latent space
    (q_nope absorbed through wk_b), so the cache is never decompressed
    to per-head K/V — the FLOP/memory saving MLA decode is built for.
    """
    b = x.shape[0]
    q, c_kv_new, k_rope_new = _latents(p, x, s, pos[None], tuner)
    # latent cache update: sequential append + triangular-prefix read,
    # TRSM-adjacent exactly like the GQA KV cache update
    ops.observe(cache.c_kv.shape[1], s.kv_lora_rank, b * s.n_heads,
                tuner, routine="trsm", site="mla.cache_update")
    cache = MLACache(
        jax.lax.dynamic_update_slice(cache.c_kv, c_kv_new, (0, pos, 0)),
        jax.lax.dynamic_update_slice(cache.k_rope, k_rope_new, (0, pos, 0)))
    valid = jnp.arange(cache.c_kv.shape[1]) <= pos
    out = _absorbed_attend(p, q, cache.c_kv, cache.k_rope, valid[None], s)
    out = out.reshape(b, 1, s.n_heads * s.v_head_dim).astype(x.dtype)
    return linear(out, p["wo"]), cache


def mla_decode_paged(p: dict, x: jax.Array, s: MLASpec, pool,
                     page_table: jax.Array, pos: jax.Array, tuner=None,
                     layer: jax.Array | None = None):
    """One-token decode against a paged latent pool (continuous batching).

    The paged twin of :func:`mla_decode`: ``pos`` is (B,) int32 per-
    sequence positions (-1 = inactive slot), ``page_table`` (B, P)
    maps logical pages to physical pages of the
    :class:`repro.models.paged_cache.PagedLatent` pool; with ``layer``,
    ``pool`` stacks every scanned layer's pools and this layer reads and
    writes ``pool[layer]``, returning the whole stack.  Same absorbed-
    projection math on the page-gathered latent view, bitwise equal
    per sequence to the contiguous path; the latent cache update keeps
    its TRSM-site recorder tag.
    """
    b = x.shape[0]
    q, c_kv_new, k_rope_new = _latents(p, x, s, pos[:, None], tuner)
    cap = page_table.shape[1] * pool.page_size
    ops.observe(cap, s.kv_lora_rank, b * s.n_heads,
                tuner, routine="trsm", site="mla.cache_update")
    active = pos >= 0
    pool = type(pool)(
        append_token(pool.c_kv, page_table, pos, c_kv_new[:, 0], active,
                     layer),
        append_token(pool.k_rope, page_table, pos, k_rope_new[:, 0],
                     active, layer))
    c_kv = gather_pages(pool.c_kv, page_table, layer)      # (B, cap, R_kv)
    k_rope = gather_pages(pool.k_rope, page_table, layer)  # (B, cap, R_rope)
    out = _absorbed_attend(p, q, c_kv, k_rope,
                           jnp.arange(cap)[None, :] <= pos[:, None], s)
    out = out.reshape(b, 1, s.n_heads * s.v_head_dim).astype(x.dtype)
    return linear(out, p["wo"]), pool
