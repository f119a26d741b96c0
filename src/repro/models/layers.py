"""Shared layer library: norms, MLPs, RoPE, GQA attention (train/prefill/
decode), chunked-softmax attention for long sequences.

All functions are pure; parameters arrive as dicts produced from the
ParamDef trees in each block builder.  Activations are (B, S, D); the
attention entry points switch between the Pallas flash kernel and the
chunked XLA path via repro.kernels.ops, and paged decode between the
Pallas paged kernel (repro.kernels.paged_attention) and an XLA gather.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.models.params import ParamDef

__all__ = [
    "rmsnorm", "layernorm", "norm_defs", "apply_norm",
    "linear", "mlp_defs", "apply_mlp",
    "rope_angles", "apply_rope",
    "attention_defs", "attention_train", "attention_decode",
    "attention_decode_paged",
    "AttnSpec", "KVCache", "init_kv_cache", "seed_kv_cache",
]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm_defs(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": ParamDef((d,), ("embed",), init="ones")}
    return {"scale": ParamDef((d,), ("embed",), init="ones"),
            "bias": ParamDef((d,), ("embed",), init="zeros")}


def apply_norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# Linear / MLP
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w: jax.Array) -> jax.Array:
    """(..., in) @ (in, out) keeping leading dims; einsum so the SPMD
    partitioner can propagate shardings without reshapes."""
    return jnp.einsum("...d,df->...f", x, w)


def mlp_defs(d: int, ff: int, kind: str) -> dict:
    if kind in ("swiglu", "geglu"):
        return {"wi": ParamDef((d, ff), ("embed", "ff")),
                "wg": ParamDef((d, ff), ("embed", "ff")),
                "wo": ParamDef((ff, d), ("ff", "embed"))}
    return {"wi": ParamDef((d, ff), ("embed", "ff")),
            "wo": ParamDef((ff, d), ("ff", "embed"))}


def apply_mlp(p: dict, x: jax.Array, kind: str, tuner=None) -> jax.Array:
    m = 1
    for dim in x.shape[:-1]:
        m *= dim
    d, ff = p["wi"].shape[-2], p["wi"].shape[-1]
    n_in = 2 * ff if kind in ("swiglu", "geglu") else ff
    ops.observe(m, d, n_in, tuner, site="mlp.in_proj")
    ops.observe(m, ff, d, tuner, site="mlp.out_proj")
    if kind == "swiglu":
        return linear(jax.nn.silu(linear(x, p["wg"])) * linear(x, p["wi"]),
                      p["wo"])
    if kind == "geglu":
        return linear(jax.nn.gelu(linear(x, p["wg"])) * linear(x, p["wi"]),
                      p["wo"])
    return linear(jax.nn.gelu(linear(x, p["wi"])), p["wo"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, dim: int,
                base: float = 10_000.0) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) of shape (..., dim/2) for integer positions."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2,
                                          dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array,
               fraction: float = 1.0) -> jax.Array:
    """Rotate the first ``fraction`` of the head dim; x: (B, S, H, Dh),
    sin/cos: (S, rot/2) — or (B, S, rot/2) when every sequence in the
    batch sits at its own position (the continuous-batching paged
    decode path, where positions are (B, S))."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    if sin.ndim == 3:     # per-sequence positions: (B, S, rot/2)
        sin_ = sin[:, :, None, : rot // 2].astype(jnp.float32)
        cos_ = cos[:, :, None, : rot // 2].astype(jnp.float32)
    else:
        sin_ = sin[None, :, None, : rot // 2].astype(jnp.float32)
        cos_ = cos[None, :, None, : rot // 2].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos_ - x2f * sin_, x2f * cos_ + x1f * sin_], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_fraction: float = 1.0
    window: int | None = None
    qk_norm: bool = False
    causal: bool = True


def attention_defs(s: AttnSpec) -> dict:
    d, h, hk, hd = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    defs = {"wq": ParamDef((d, h * hd), ("embed", "heads")),
            "wk": ParamDef((d, hk * hd), ("embed", "kv_heads")),
            "wv": ParamDef((d, hk * hd), ("embed", "kv_heads")),
            "wo": ParamDef((h * hd, d), ("heads", "embed"))}
    if s.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="ones")
        defs["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return defs


def _project_qkv(p: dict, x: jax.Array, s: AttnSpec, positions: jax.Array,
                 tuner=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, sq, d = x.shape
    # the q/k/v projections are plain GEMMs; tag them so the recorded
    # routine mix carries the dense dispatch volume, not just the
    # SYRK/TRSM-eligible sites
    ops.observe(b * sq, d,
                (s.n_heads + 2 * s.n_kv_heads) * s.head_dim, tuner,
                site="attn.qkv_proj")
    q = linear(x, p["wq"]).reshape(b, sq, s.n_heads, s.head_dim)
    k = linear(x, p["wk"]).reshape(b, sq, s.n_kv_heads, s.head_dim)
    v = linear(x, p["wv"]).reshape(b, sq, s.n_kv_heads, s.head_dim)
    if s.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if s.rope_fraction > 0:
        sin, cos = rope_angles(positions, int(s.head_dim * s.rope_fraction))
        q = apply_rope(q, sin, cos, 1.0 if s.rope_fraction == 1.0
                       else s.rope_fraction)
        k = apply_rope(k, sin, cos, 1.0 if s.rope_fraction == 1.0
                       else s.rope_fraction)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, H, D) by repeating each KV head."""
    b, sq, hk, hd = k.shape
    if hk == n_heads:
        return k
    rep = n_heads // hk
    return jnp.repeat(k, rep, axis=2)


def chunked_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool, window: int | None,
                          q_offset: int = 0,
                          chunk: int = 512) -> jax.Array:
    """Online-softmax attention, scanned over query chunks (XLA path).

    Never materialises the full (Sq, Skv) score matrix: per scan step the
    live score block is (B, H, chunk, Skv).  q/k/v: (B, H, S, D).
    """
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    dv = v.shape[3]
    scale = dh ** -0.5
    nc = -(-sq // chunk)
    pad = nc * chunk - sq
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qc = qp.reshape(b, h, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    kv_ids = jnp.arange(skv)

    def step(_, qi_ci):
        qi, ci = qi_ci
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        q_ids = q_offset + ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, skv), dtype=bool)
        if causal:
            mask &= kv_ids[None, :] <= q_ids[:, None]
        if window is not None:
            mask &= kv_ids[None, :] > q_ids[:, None] - window
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(step, None, (qc, jnp.arange(nc)))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, nc * chunk, dv)
    return out[:, :, :sq]


def attention_train(p: dict, x: jax.Array, s: AttnSpec, tuner=None,
                    backend: str = "auto"
                    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence self-attention (training / prefill internals).

    Returns (out, (k, v)) — the pre-repeat (B, S, Hkv, Dh) projections so
    prefill can seed the decode cache without recomputation.

    The attention core is one :func:`ops.flash_attention` dispatch on
    the flattened (B*H, Sq, Dh) heads: causal (and sliding-window)
    layers dispatch as routine="attn" and the tuner resolves the flash
    blocks, the dense vs block-sparse triangular KV grid, and — on the
    XLA backend — whether the SYRK score-materialisation path wins for
    this shape (recorded as routine="syrk" through ops.syrk, like the
    retired fixed-threshold lowering).  Non-causal unwindowed layers
    stay gemm-tagged.  ``backend`` is passed to the dispatch (see
    :attr:`repro.models.transformer.Ctx.backend`).
    """
    b, sq, _ = x.shape
    positions = jnp.arange(sq)
    q, k, v = _project_qkv(p, x, s, positions, tuner)
    kr = _repeat_kv(k, s.n_heads)
    vr = _repeat_kv(v, s.n_heads)
    qt = q.transpose(0, 2, 1, 3)           # (B, H, S, Dh)
    kt = kr.transpose(0, 2, 1, 3)
    vt = vr.transpose(0, 2, 1, 3)
    flat = (b * s.n_heads, sq, s.head_dim)
    out = ops.flash_attention(qt.reshape(flat), kt.reshape(flat),
                              vt.reshape(flat), causal=s.causal,
                              window=s.window, tuner=tuner,
                              backend=backend, site="attn.core")
    out = out.reshape(b, s.n_heads, sq, s.head_dim).transpose(0, 2, 1, 3)
    ops.observe(b * sq, s.n_heads * s.head_dim, x.shape[-1], tuner,
                site="attn.out_proj")
    out = linear(out.reshape(b, sq, s.n_heads * s.head_dim), p["wo"])
    return out, (k, v)


def seed_kv_cache(k: jax.Array, v: jax.Array, capacity: int, *,
                  windowed: bool, quantized: bool = False) -> KVCache:
    """Build the decode cache from prefill projections k/v (B, S, Hkv, D).

    Full cache: first S slots filled.  Ring cache: the last ``capacity``
    positions land at slot = pos % capacity (a cyclic roll).
    """
    b, sq, hk, hd = k.shape
    if not windowed:
        pad = capacity - sq
        if pad < 0:
            raise ValueError(f"prompt {sq} exceeds cache {capacity}")
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    elif sq >= capacity:
        shift = sq % capacity
        kc = jnp.roll(k[:, -capacity:], shift, axis=1)
        vc = jnp.roll(v[:, -capacity:], shift, axis=1)
    else:
        pad = capacity - sq
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if quantized:
        kq, ks = _quantize_kv(kc)
        vq, vs = _quantize_kv(vc)
        return KVCache(kq, vq, windowed, ks, vs)
    return KVCache(kc, vc, windowed)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KVCache:
    """Either a full cache (capacity = max seq) or a ring buffer
    (capacity = window) for sliding-window layers.

    Optionally int8-quantised (beyond-paper §Perf optimisation for
    memory-bound MHA decode): k/v stored int8 with a per-(batch, slot,
    head) fp16 scale — 2.06x fewer cache bytes than bf16."""
    k: jax.Array            # (B, cap, Hkv, Dh) — bf16/f32 or int8
    v: jax.Array
    windowed: bool
    k_scale: jax.Array | None = None   # (B, cap, Hkv) when quantised
    v_scale: jax.Array | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_kv_cache(batch: int, capacity: int, n_kv_heads: int, head_dim: int,
                  dtype: jnp.dtype, *, windowed: bool = False,
                  quantized: bool = False) -> KVCache:
    shape = (batch, capacity, n_kv_heads, head_dim)
    if quantized:
        sshape = (batch, capacity, n_kv_heads)
        return KVCache(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape, jnp.int8), windowed,
                       jnp.ones(sshape, jnp.float16),
                       jnp.ones(sshape, jnp.float16))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   windowed)


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "k_scale", "v_scale"],
    meta_fields=["windowed"])


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, S, H, D) -> int8 values + per-(B, S, H) fp16 scales."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q: jax.Array, scale: jax.Array,
                   dtype: jnp.dtype) -> jax.Array:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def attention_decode(p: dict, x: jax.Array, s: AttnSpec, cache: KVCache,
                     pos: jax.Array, tuner=None
                     ) -> tuple[jax.Array, KVCache]:
    """One-token decode: x (B, 1, D); pos scalar int32 (tokens so far).

    The cache update is TRSM-adjacent: each step appends one row and
    reads the triangular valid prefix, a sequential dependency along
    the cache axis exactly like TRSM's M-panel substitution — so the
    (cap, Dh, B*H) contraction is tagged routine="trsm" (degrading to
    gemm on artifacts without trsm signal) rather than priced as a
    parallel GEMM.
    """
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, s, pos[None], tuner)
    cap = cache.k.shape[1]
    ops.observe(cap, s.head_dim, b * s.n_heads, tuner,
                routine="trsm", site="attn.cache_update")
    slot = pos % cap if cache.windowed else jnp.minimum(pos, cap - 1)
    if cache.quantized:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        kc = jax.lax.dynamic_update_slice(cache.k, kq, (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache.v, vq, (0, slot, 0, 0))
        ksc = jax.lax.dynamic_update_slice(cache.k_scale, ks, (0, slot, 0))
        vsc = jax.lax.dynamic_update_slice(cache.v_scale, vs, (0, slot, 0))
        new_cache = KVCache(kc, vc, cache.windowed, ksc, vsc)
        k = _dequantize_kv(kc, ksc, x.dtype)
        v = _dequantize_kv(vc, vsc, x.dtype)
    else:
        k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, slot, 0, 0))
        new_cache = KVCache(k, v, cache.windowed)

    kk = _repeat_kv(k, s.n_heads)
    vv = _repeat_kv(v, s.n_heads)
    scores = jnp.einsum("bohd,bkhd->bhk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (s.head_dim ** -0.5)
    kv_ids = jnp.arange(cap)
    if cache.windowed:
        # ring buffer: valid slots are the last min(pos+1, cap) writes
        age = (slot - kv_ids) % cap
        valid = age < jnp.minimum(pos + 1, cap)
    else:
        valid = kv_ids <= pos
    scores = jnp.where(valid[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vv.astype(jnp.float32))
    out = out.reshape(b, 1, s.n_heads * s.head_dim).astype(x.dtype)
    ops.observe(b, s.n_heads * s.head_dim, x.shape[-1], tuner,
                site="attn.out_proj")
    return linear(out, p["wo"]), new_cache


def attention_decode_paged(p: dict, x: jax.Array, s: AttnSpec, pool,
                           page_table: jax.Array, pos: jax.Array,
                           tuner=None, backend: str = "auto"):
    """One-token decode against a paged KV pool (continuous batching).

    x (B, 1, D); ``pos`` is (B,) int32 — every sequence in the batch
    sits at its own position (ragged admission), with -1 marking an
    inactive batch slot; ``page_table`` (B, P) int32 maps each
    sequence's logical pages to physical pages of ``pool``
    (:class:`repro.serve.kv_cache.PagedKV`), -1 marking holes.

    Where ``backend`` resolves to ``"pallas"`` the attention core is
    :func:`repro.kernels.paged_attention.paged_decode_attention_pallas`:
    it reads only each slot's live pages, in the pool's dtype, and
    scores the G query heads of a KV head against one load of its rows.
    Its arithmetic is the XLA path's at f32, with the softmax summed
    block by block, so the two agree to f32 rounding.

    The XLA path is element-for-element the fixed-batch
    :func:`attention_decode`: the page gather materialises the same
    (B, cap, Hkv, Dh) view the contiguous cache holds (holes land
    beyond the ``kv_ids <= pos`` valid prefix where the mask erases
    them), so on this path per-sequence outputs are bitwise identical
    to the fixed-batch path — the scheduler's golden-parity contract.
    The cache update keeps its TRSM-site recorder tag: still a
    sequential append + triangular-prefix read, just scattered through
    the page table.
    """
    from repro.serve.kv_cache import append_token, gather_pages

    if s.window is not None:
        raise NotImplementedError(
            "paged decode does not support sliding-window (ring) caches")
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, s, pos[:, None], tuner)
    cap = page_table.shape[1] * pool.page_size
    ops.observe(cap, s.head_dim, b * s.n_heads, tuner,
                routine="trsm", site="attn.cache_update")
    active = pos >= 0
    pool = type(pool)(
        append_token(pool.k, page_table, pos, k_new[:, 0].reshape(b, -1),
                     active),
        append_token(pool.v, page_table, pos, v_new[:, 0].reshape(b, -1),
                     active))
    if ops.resolve_backend(backend) == "pallas":
        out = paged_decode_attention_pallas(
            q[:, 0], pool.k, pool.v, jnp.where(active, pos + 1, 0),
            page_table, interpret=ops.resolve_interpret())
    else:
        kv = (b, cap, s.n_kv_heads, s.head_dim)
        k = gather_pages(pool.k, page_table).reshape(kv)
        v = gather_pages(pool.v, page_table).reshape(kv)
        kk = _repeat_kv(k, s.n_heads)
        vv = _repeat_kv(v, s.n_heads)
        scores = jnp.einsum("bohd,bkhd->bhk", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * (s.head_dim ** -0.5)
        valid = jnp.arange(cap)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhk,bkhd->bhd", probs, vv.astype(jnp.float32))
    out = out.reshape(b, 1, s.n_heads * s.head_dim).astype(x.dtype)
    ops.observe(b, s.n_heads * s.head_dim, x.shape[-1], tuner,
                site="attn.out_proj")
    return linear(out, p["wo"]), pool
