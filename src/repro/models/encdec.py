"""Encoder-decoder LM (whisper-tiny backbone).

The conv/mel frontend is a STUB per the assignment: ``input_specs``
supplies precomputed frame embeddings (B, encoder_len, D).  The
transformer backbone is faithful: non-causal encoder self-attention,
causal decoder self-attention + cross-attention, learned positional
embeddings, LayerNorm + GELU MLPs.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.models.params import ParamDef, init_params, param_specs
from repro.models.transformer import Ctx, chunked_cross_entropy

__all__ = ["EncDecLM", "build_encdec"]


def _attn_spec(cfg: ArchConfig, causal: bool) -> L.AttnSpec:
    return L.AttnSpec(d_model=cfg.d_model, n_heads=cfg.n_heads,
                      n_kv_heads=cfg.n_kv_heads,
                      head_dim=cfg.resolved_head_dim,
                      rope_fraction=0.0, causal=causal)


def _enc_layer_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    return {"ln1": L.norm_defs(d, cfg.norm_kind),
            "attn": L.attention_defs(_attn_spec(cfg, causal=False)),
            "ln2": L.norm_defs(d, cfg.norm_kind),
            "mlp": L.mlp_defs(d, cfg.d_ff, cfg.mlp_kind)}


def _dec_layer_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    return {"ln1": L.norm_defs(d, cfg.norm_kind),
            "self_attn": L.attention_defs(_attn_spec(cfg, causal=True)),
            "ln_x": L.norm_defs(d, cfg.norm_kind),
            "cross_attn": L.attention_defs(_attn_spec(cfg, causal=False)),
            "ln2": L.norm_defs(d, cfg.norm_kind),
            "mlp": L.mlp_defs(d, cfg.d_ff, cfg.mlp_kind)}


def _cross_attention(p: dict, x: jax.Array, enc_k: jax.Array,
                     enc_v: jax.Array, s: L.AttnSpec,
                     tuner=None) -> jax.Array:
    """Query from x, K/V precomputed from encoder output."""
    b, sq, _ = x.shape
    # cross-attention scores are rectangular (decoder x encoder): a
    # plain GEMM, never SYRK-eligible — tagged so the recorded mix
    # distinguishes it from causal self-attention
    ops.observe(sq, s.head_dim, enc_k.shape[1], tuner,
                site="attn.cross_qk", count=b * s.n_heads)
    q = L.linear(x, p["wq"]).reshape(b, sq, s.n_heads, s.head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        enc_k.astype(jnp.float32)) * (s.head_dim ** -0.5)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                     enc_v.astype(jnp.float32)).astype(x.dtype)
    return L.linear(out.reshape(b, sq, s.n_heads * s.head_dim), p["wo"])


def _project_enc_kv(p: dict, enc: jax.Array, s: L.AttnSpec
                    ) -> tuple[jax.Array, jax.Array]:
    b, sk, _ = enc.shape
    k = L.linear(enc, p["wk"]).reshape(b, sk, s.n_kv_heads, s.head_dim)
    v = L.linear(enc, p["wv"]).reshape(b, sk, s.n_kv_heads, s.head_dim)
    return (L._repeat_kv(k, s.n_heads), L._repeat_kv(v, s.n_heads))


class EncDecLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        d = cfg.d_model
        self.defs = {
            "embed": ParamDef((cfg.vocab, d), ("vocab", "embed"), scale=1.0),
            "pos_dec": ParamDef((32_768, d), (None, "embed"), scale=0.02),
            "pos_enc": ParamDef((cfg.encoder_len, d), (None, "embed"),
                                scale=0.02),
            "encoder": [_enc_layer_defs(cfg)
                        for _ in range(cfg.n_encoder_layers)],
            "ln_enc": L.norm_defs(d, cfg.norm_kind),
            "decoder": [_dec_layer_defs(cfg) for _ in range(cfg.n_layers)],
            "ln_f": L.norm_defs(d, cfg.norm_kind),
        }

    def init(self, rng: jax.Array, dtype=jnp.float32) -> dict:
        return init_params(self.defs, rng, dtype)

    def param_partition_specs(self, rules: dict) -> dict:
        return param_specs(self.defs, rules)

    # -- encoder -----------------------------------------------------------
    def encode(self, params: dict, audio_emb: jax.Array,
               tuner=None, backend: str = "auto") -> jax.Array:
        cfg = self.cfg
        x = audio_emb + params["pos_enc"][None, : audio_emb.shape[1]]
        spec = _attn_spec(cfg, causal=False)
        for p in params["encoder"]:
            h, _ = L.attention_train(
                p["attn"], L.apply_norm(p["ln1"], x, cfg.norm_kind), spec,
                tuner=tuner, backend=backend)
            x = x + h
            x = x + L.apply_mlp(
                p["mlp"], L.apply_norm(p["ln2"], x, cfg.norm_kind),
                cfg.mlp_kind, tuner=tuner)
        return L.apply_norm(params["ln_enc"], x, cfg.norm_kind)

    # -- decoder full-sequence ----------------------------------------------
    def _decode_seq(self, params: dict, tokens: jax.Array, enc: jax.Array,
                    ctx: Ctx) -> tuple[jax.Array, list]:
        cfg = self.cfg
        want_cache = ctx.mode == "prefill"
        x = params["embed"][tokens] + params["pos_dec"][None,
                                                        : tokens.shape[1]]
        sa = _attn_spec(cfg, causal=True)
        ca = _attn_spec(cfg, causal=False)
        caches = []
        for p in params["decoder"]:
            h, kv = L.attention_train(
                p["self_attn"], L.apply_norm(p["ln1"], x, cfg.norm_kind),
                sa, tuner=ctx.tuner, backend=ctx.backend)
            x = x + h
            ek, ev = _project_enc_kv(p["cross_attn"], enc, ca)
            x = x + _cross_attention(
                p["cross_attn"], L.apply_norm(p["ln_x"], x, cfg.norm_kind),
                ek, ev, ca, tuner=ctx.tuner)
            x = x + L.apply_mlp(
                p["mlp"], L.apply_norm(p["ln2"], x, cfg.norm_kind),
                cfg.mlp_kind, tuner=ctx.tuner)
            if want_cache:
                caches.append({
                    "self": L.seed_kv_cache(kv[0], kv[1], ctx.cache_len,
                                            windowed=False),
                    "cross_k": ek, "cross_v": ev})
        return L.apply_norm(params["ln_f"], x, cfg.norm_kind), caches

    # -- public API -----------------------------------------------------------
    def loss(self, params: dict, batch: dict, ctx: Ctx | None = None
             ) -> jax.Array:
        ctx = ctx or Ctx(mode="train")
        enc = self.encode(params, batch["audio_emb"], tuner=ctx.tuner,
                          backend=ctx.backend)
        x, _ = self._decode_seq(params, batch["tokens"], enc, ctx)
        return chunked_cross_entropy(x, params["embed"].T, batch["labels"])

    def prefill(self, params: dict, batch: dict, ctx: Ctx
                ) -> tuple[jax.Array, list]:
        enc = self.encode(params, batch["audio_emb"], tuner=ctx.tuner,
                          backend=ctx.backend)
        x, caches = self._decode_seq(params, batch["tokens"], enc, ctx)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["embed"].T)
        return logits, caches

    def init_cache(self, batch: int, ctx: Ctx, dtype=jnp.float32) -> list:
        cfg = self.cfg
        sa = _attn_spec(cfg, causal=True)
        return [{
            "self": L.init_kv_cache(batch, ctx.cache_len, sa.n_kv_heads,
                                    sa.head_dim, dtype),
            "cross_k": jnp.zeros((batch, cfg.encoder_len, cfg.n_heads,
                                  sa.head_dim), dtype),
            "cross_v": jnp.zeros((batch, cfg.encoder_len, cfg.n_heads,
                                  sa.head_dim), dtype),
        } for _ in range(cfg.n_layers)]

    def decode_step(self, params: dict, token: jax.Array, cache: list,
                    pos: jax.Array, ctx: Ctx) -> tuple[jax.Array, list]:
        cfg = self.cfg
        x = params["embed"][token] + jax.lax.dynamic_slice_in_dim(
            params["pos_dec"], pos, 1, axis=0)[None]
        sa = _attn_spec(cfg, causal=True)
        ca = _attn_spec(cfg, causal=False)
        new_cache = []
        for p, c in zip(params["decoder"], cache):
            h, self_c = L.attention_decode(
                p["self_attn"], L.apply_norm(p["ln1"], x, cfg.norm_kind),
                sa, c["self"], pos, tuner=ctx.tuner)
            x = x + h
            x = x + _cross_attention(
                p["cross_attn"], L.apply_norm(p["ln_x"], x, cfg.norm_kind),
                c["cross_k"], c["cross_v"], ca, tuner=ctx.tuner)
            x = x + L.apply_mlp(
                p["mlp"], L.apply_norm(p["ln2"], x, cfg.norm_kind),
                cfg.mlp_kind, tuner=ctx.tuner)
            new_cache.append({"self": self_c, "cross_k": c["cross_k"],
                              "cross_v": c["cross_v"]})
        x = L.apply_norm(params["ln_f"], x, cfg.norm_kind)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["embed"].T)
        return logits, new_cache


def build_encdec(cfg: ArchConfig) -> EncDecLM:
    return EncDecLM(cfg)
