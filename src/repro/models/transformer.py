"""Decoder-only LM assembly for all non-encoder-decoder families.

Layers are grouped into
  prefix   — unrolled leading layers (e.g. deepseek's dense layer 0),
  unit x R — the repeating pattern scanned with ``lax.scan`` (keeps the
             HLO small: one unit body regardless of depth),
  suffix   — unrolled remainder when n_layers is not a multiple of the
             pattern length (e.g. recurrentgemma's 26 = 3*8 + 2).

The same layer-apply code serves train, prefill (returns caches) and
decode (consumes caches), so there is exactly one implementation of each
block to test.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import recurrent as REC
from repro.models import xlstm as XL
from repro.models.config import ArchConfig, ShapeSpec
from repro.models.params import ParamDef, init_params, param_specs

__all__ = ["LM", "build_lm", "chunked_cross_entropy"]


# ---------------------------------------------------------------------------
# Layer taxonomy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str      # attn | local | rglru | mlstm | slstm
    mlp: str       # mlp | moe | none
    d_ff: int = 0  # per-layer ff width (deepseek dense layer differs)


def _layer_plan(cfg: ArchConfig) -> list[LayerSpec]:
    plan = []
    pattern = cfg.pattern or ("attn",)
    for i in range(cfg.n_layers):
        kind = pattern[i % len(pattern)]
        if cfg.n_experts and i >= cfg.first_dense_layers:
            mlp = "moe"
            ff = cfg.d_ff_expert or cfg.d_ff
        elif cfg.mlp_kind == "none":
            mlp, ff = "none", 0
        else:
            mlp = "mlp"
            ff = (cfg.d_ff_dense
                  if cfg.n_experts and i < cfg.first_dense_layers
                  else cfg.d_ff)
        plan.append(LayerSpec(kind, mlp, ff))
    return plan


def _segments(plan: list[LayerSpec]
              ) -> tuple[list[LayerSpec], list[LayerSpec], int,
                         list[LayerSpec]]:
    """(prefix, unit, repeats, suffix) with unit = shortest cycle."""
    # prefix = leading layers that differ from the eventual cycle
    # find the cycle of the tail: try cycle lengths 1..4
    for start in range(0, min(4, len(plan))):
        tail = plan[start:]
        for clen in (1, 2, 3, 4):
            if clen > len(tail):
                break
            unit = tail[:clen]
            reps = len(tail) // clen
            if reps >= 1 and all(
                    tail[i] == unit[i % clen] for i in range(reps * clen)):
                suffix = tail[reps * clen:]
                return plan[:start], unit, reps, suffix
    return plan, [], 0, []          # fully unrolled fallback


# ---------------------------------------------------------------------------
# Per-layer defs / apply
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ArchConfig, kind: str) -> L.AttnSpec:
    return L.AttnSpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        rope_fraction=cfg.rope_fraction,
        window=(cfg.local_window if kind == "local" else cfg.window),
        qk_norm=cfg.qk_norm)


def _mla_spec(cfg: ArchConfig) -> MLA.MLASpec:
    return MLA.MLASpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim)


def _moe_spec(cfg: ArchConfig) -> MOE.MoESpec:
    return MOE.MoESpec(
        d_model=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_ff=cfg.d_ff_expert or cfg.d_ff, n_shared=cfg.n_shared_experts)


def _rglru_spec(cfg: ArchConfig) -> REC.RGLRUSpec:
    return REC.RGLRUSpec(d_model=cfg.d_model,
                         width=cfg.lru_width or cfg.d_model,
                         conv_width=cfg.conv_width)


def _xlstm_spec(cfg: ArchConfig) -> XL.XLSTMSpec:
    return XL.XLSTMSpec(d_model=cfg.d_model, n_heads=cfg.n_heads)


def _layer_defs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    defs: dict = {"ln1": L.norm_defs(d, cfg.norm_kind)}
    if spec.kind in ("attn", "local"):
        if cfg.attn_kind == "mla":
            defs["mixer"] = MLA.mla_defs(_mla_spec(cfg))
        else:
            defs["mixer"] = L.attention_defs(_attn_spec(cfg, spec.kind))
    elif spec.kind == "rglru":
        defs["mixer"] = REC.rglru_block_defs(_rglru_spec(cfg))
    elif spec.kind == "mlstm":
        defs["mixer"] = XL.mlstm_defs(_xlstm_spec(cfg))
    elif spec.kind == "slstm":
        defs["mixer"] = XL.slstm_defs(_xlstm_spec(cfg))
    else:
        raise ValueError(spec.kind)
    if spec.mlp == "mlp":
        defs["ln2"] = L.norm_defs(d, cfg.norm_kind)
        defs["mlp"] = L.mlp_defs(d, spec.d_ff, cfg.mlp_kind)
    elif spec.mlp == "moe":
        defs["ln2"] = L.norm_defs(d, cfg.norm_kind)
        defs["moe"] = MOE.moe_defs(_moe_spec(cfg))
    return defs


# ---------------------------------------------------------------------------
# Runtime context: mode + mesh info
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ctx:
    mode: str                      # train | prefill | decode
    mesh: Any = None               # jax Mesh for the shard_map MoE path
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    cache_len: int = 0             # decode capacity
    remat: bool = True
    kv_quantized: bool = False     # int8 KV cache (§Perf, memory-bound
                                   # decode cells)
    tuner: Any = None              # AdsalaTuner threaded to every
                                   # routine-aware call site (None = the
                                   # sites still report dispatch events,
                                   # just untuned)

    @property
    def backend(self) -> str:
        """Kernel backend (:func:`repro.kernels.ops.resolve_backend`) for
        the call sites XLA partitions or differentiates.

        Pallas kernels define no VJP, and Mosaic kernels cannot be
        partitioned by GSPMD (only inside ``shard_map``), so train steps
        and meshed steps take the XLA path; an unmeshed prefill or
        decode resolves as usual (the tuned kernels on a TPU).
        """
        if self.mode == "train" or self.mesh is not None:
            return "xla"
        return "auto"


def _moe_apply(p: dict, x: jax.Array, cfg: ArchConfig, ctx: Ctx
               ) -> tuple[jax.Array, jax.Array]:
    """MoE dispatch-path selection.

    * no mesh / decode step  -> dense one-hot path (tiny workloads),
    * E divisible by tp size -> shard_map expert parallelism (deepseek),
    * otherwise              -> shard_map expert tensor parallelism
                                (mixtral: 8 experts on a 16-way axis).
    """
    spec = _moe_spec(cfg)
    if ctx.mesh is None or ctx.mode == "decode":
        return MOE.apply_moe(p, x, spec, tuner=ctx.tuner,
                             backend=ctx.backend)
    from jax.sharding import PartitionSpec as P
    dp = ctx.dp_axes
    tp = ctx.tp_axis
    tp_size = ctx.mesh.shape[tp]
    ep_mode = (spec.n_experts % tp_size == 0
               and x.shape[1] % tp_size == 0)
    spec = dataclasses.replace(spec, ep_axis=tp)
    fn = MOE.apply_moe_ep if ep_mode else MOE.apply_moe_tp
    # inside shard_map each device runs its own kernel call: only
    # autodiff (train mode) still needs the XLA path
    backend = "xla" if ctx.mode == "train" else "auto"

    def wrapped(p_local, x_local):
        out, aux = fn(p_local, x_local, s=spec, tuner=ctx.tuner,
                      backend=backend)
        return out, jax.lax.pmean(aux, (*dp, tp))

    if ep_mode:
        w_specs = {k: (P() if k.startswith(("router", "shared"))
                       else P(tp, None, None)) for k in p}
        x_spec = P(dp, tp, None)
    else:
        w_specs = {}
        for k in p:
            if k.startswith(("router", "shared")):
                w_specs[k] = P()
            elif k == "wo":
                w_specs[k] = P(None, tp, None)
            else:
                w_specs[k] = P(None, None, tp)
        x_spec = P(dp, None, None)
    return jax.shard_map(
        wrapped, mesh=ctx.mesh,
        in_specs=(w_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)(p, x)


def _seed_cache(raw: Any, cfg: ArchConfig, spec: LayerSpec,
                ctx: Ctx) -> Any:
    """Convert a mixer's prefill by-product into decode cache format."""
    if spec.kind in ("attn", "local"):
        if cfg.attn_kind == "mla":
            c_kv, k_rope = raw
            return MLA.seed_mla_cache(c_kv, k_rope, ctx.cache_len)
        a = _attn_spec(cfg, spec.kind)
        windowed = a.window is not None
        cap = min(ctx.cache_len, a.window) if windowed else ctx.cache_len
        k, v = raw
        return L.seed_kv_cache(k, v, cap, windowed=windowed,
                               quantized=ctx.kv_quantized)
    return raw  # recurrent states are already decode-format


def _apply_layer_train(p: dict, x: jax.Array, cfg: ArchConfig,
                       spec: LayerSpec, ctx: Ctx
                       ) -> tuple[jax.Array, jax.Array, Any]:
    """Full-sequence layer application.

    Returns (x, aux_loss, cache) — cache is decode-format when
    ctx.mode == 'prefill', else None (so train carries no dead weight).
    """
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["ln1"], x, cfg.norm_kind)
    if spec.kind in ("attn", "local"):
        if cfg.attn_kind == "mla":
            mix, raw = MLA.mla_train(p["mixer"], h, _mla_spec(cfg),
                                     tuner=ctx.tuner)
        else:
            mix, raw = L.attention_train(p["mixer"], h,
                                         _attn_spec(cfg, spec.kind),
                                         tuner=ctx.tuner,
                                         backend=ctx.backend)
    elif spec.kind == "rglru":
        mix, raw = REC.rglru_block_train(p["mixer"], h)
    elif spec.kind == "mlstm":
        mix, raw = XL.mlstm_train(p["mixer"], h, _xlstm_spec(cfg))
    else:
        mix, raw = XL.slstm_train(p["mixer"], h, _xlstm_spec(cfg))
    cache = _seed_cache(raw, cfg, spec, ctx) if ctx.mode == "prefill" \
        else None
    x = x + mix
    if spec.mlp == "mlp":
        x = x + L.apply_mlp(p["mlp"],
                            L.apply_norm(p["ln2"], x, cfg.norm_kind),
                            cfg.mlp_kind, tuner=ctx.tuner)
    elif spec.mlp == "moe":
        out, aux = _moe_apply(p["moe"],
                              L.apply_norm(p["ln2"], x, cfg.norm_kind),
                              cfg, ctx)
        x = x + out
    return x, aux, cache


# --- caches ----------------------------------------------------------------

def _init_layer_cache(cfg: ArchConfig, spec: LayerSpec, batch: int,
                      ctx: Ctx, dtype) -> Any:
    if spec.kind in ("attn", "local"):
        if cfg.attn_kind == "mla":
            return MLA.init_mla_cache(batch, ctx.cache_len, _mla_spec(cfg),
                                      dtype)
        a = _attn_spec(cfg, spec.kind)
        windowed = a.window is not None
        cap = min(ctx.cache_len, a.window) if windowed else ctx.cache_len
        return L.init_kv_cache(batch, cap, a.n_kv_heads, a.head_dim,
                               dtype, windowed=windowed,
                               quantized=ctx.kv_quantized)
    if spec.kind == "rglru":
        return REC.init_rglru_state(batch, _rglru_spec(cfg), dtype)
    if spec.kind == "mlstm":
        return XL.init_mlstm_state(batch, _xlstm_spec(cfg))
    return XL.init_slstm_state(batch, _xlstm_spec(cfg))


def _init_layer_paged(cfg: ArchConfig, spec: LayerSpec, n_pages: int,
                      page_size: int, ctx: Ctx, dtype) -> Any:
    """Paged twin of :func:`_init_layer_cache`: one page pool per
    attention layer.  Sliding-window (ring) and recurrent-state layers
    have no paged representation (the window bounds their memory
    already; recurrent states carry no sequence dim) — continuous
    batching supports the attention-cache families."""
    from repro.serve import kv_cache as KV

    if spec.kind not in ("attn", "local"):
        raise NotImplementedError(
            f"paged decode cache for layer kind {spec.kind!r} "
            "(recurrent states are not paged)")
    if ctx.kv_quantized:
        raise NotImplementedError("paged decode with int8 KV cache")
    if cfg.attn_kind == "mla":
        s = _mla_spec(cfg)
        return KV.init_paged_latent(n_pages, page_size, s.kv_lora_rank,
                                    s.qk_rope_dim, dtype)
    a = _attn_spec(cfg, spec.kind)
    if a.window is not None:
        raise NotImplementedError(
            "paged decode cache for sliding-window (ring) layers")
    return KV.init_paged_kv(n_pages, page_size, a.n_kv_heads,
                            a.head_dim, dtype)


def _apply_layer_decode(p: dict, x: jax.Array, cache: Any,
                        pos: jax.Array, cfg: ArchConfig, spec: LayerSpec,
                        ctx: Ctx, page_table: jax.Array | None = None
                        ) -> tuple[jax.Array, Any]:
    """``page_table`` switches the attention mixers onto the paged read
    path (cache leaves are PagedKV/PagedLatent pools, ``pos`` is (B,)
    per-sequence positions) — the continuous-batching decode."""
    h = L.apply_norm(p["ln1"], x, cfg.norm_kind)
    if spec.kind in ("attn", "local"):
        if page_table is not None:
            if cfg.attn_kind == "mla":
                mix, cache = MLA.mla_decode_paged(
                    p["mixer"], h, _mla_spec(cfg), cache, page_table,
                    pos, tuner=ctx.tuner)
            else:
                mix, cache = L.attention_decode_paged(
                    p["mixer"], h, _attn_spec(cfg, spec.kind), cache,
                    page_table, pos, tuner=ctx.tuner,
                    backend=ctx.backend)
        elif cfg.attn_kind == "mla":
            mix, cache = MLA.mla_decode(p["mixer"], h, _mla_spec(cfg),
                                        cache, pos, tuner=ctx.tuner)
        else:
            mix, cache = L.attention_decode(
                p["mixer"], h, _attn_spec(cfg, spec.kind), cache, pos,
                tuner=ctx.tuner)
    elif spec.kind == "rglru":
        mix, cache = REC.rglru_block_decode(p["mixer"], h, cache)
    elif spec.kind == "mlstm":
        mix, cache = XL.mlstm_decode(p["mixer"], h, _xlstm_spec(cfg), cache)
    else:
        mix, cache = XL.slstm_decode(p["mixer"], h, _xlstm_spec(cfg), cache)
    x = x + mix
    if spec.mlp == "mlp":
        x = x + L.apply_mlp(p["mlp"],
                            L.apply_norm(p["ln2"], x, cfg.norm_kind),
                            cfg.mlp_kind, tuner=ctx.tuner)
    elif spec.mlp == "moe":
        out, _ = _moe_apply(p["moe"],
                            L.apply_norm(p["ln2"], x, cfg.norm_kind),
                            cfg, ctx)
        x = x + out
    return x, cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def chunked_cross_entropy(x: jax.Array, w_unemb: jax.Array,
                          labels: jax.Array, *, chunk: int = 512
                          ) -> jax.Array:
    """Mean CE over (B, S) without materialising (B, S, V) at once."""
    b, s, d = x.shape
    nc = -(-s // chunk)
    pad = nc * chunk - s
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = xp.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = lp.reshape(b, nc, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        xi, li = inp
        logits = jnp.einsum("bcd,dv->bcv", xi, w_unemb).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        ce = ((logz - gold) * valid).sum()
        return carry + jnp.stack([ce, valid.sum()]), None

    tot, _ = jax.lax.scan(step, jnp.zeros(2, jnp.float32), (xc, lc))
    return tot[0] / jnp.maximum(tot[1], 1.0)


# ---------------------------------------------------------------------------
# The model object
# ---------------------------------------------------------------------------

class LM:
    """Decoder-only LM with scan-over-pattern distribution-ready layout."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        plan = _layer_plan(cfg)
        self.prefix, self.unit, self.repeats, self.suffix = _segments(plan)
        self.defs = self._build_defs()

    # -- parameter definitions ---------------------------------------------
    def _build_defs(self) -> dict:
        cfg = self.cfg
        defs: dict = {
            "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                              scale=1.0),
            "ln_f": L.norm_defs(cfg.d_model, cfg.norm_kind),
        }
        if not cfg.tie_embeddings:
            defs["unembed"] = ParamDef((cfg.d_model, cfg.vocab),
                                       ("embed", "vocab"))
        defs["prefix"] = [
            _layer_defs(cfg, s) for s in self.prefix]
        if self.repeats:
            unit_defs = [_layer_defs(cfg, s) for s in self.unit]
            defs["scan"] = jax.tree.map(
                lambda d: ParamDef((self.repeats,) + d.shape,
                                   ("layers",) + d.axes, init=d.init,
                                   scale=d.scale),
                unit_defs, is_leaf=lambda v: isinstance(v, ParamDef))
        defs["suffix"] = [
            _layer_defs(cfg, s) for s in self.suffix]
        return defs

    def init(self, rng: jax.Array, dtype=jnp.float32) -> dict:
        return init_params(self.defs, rng, dtype)

    def param_partition_specs(self, rules: dict) -> dict:
        return param_specs(self.defs, rules)

    # -- forward --------------------------------------------------------------
    def _embed(self, params: dict, tokens: jax.Array) -> jax.Array:
        return params["embed"][tokens]

    def _unembed_weight(self, params: dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["unembed"]

    def forward(self, params: dict, tokens: jax.Array, ctx: Ctx
                ) -> tuple[jax.Array, jax.Array, dict | None]:
        """(B, S) tokens -> (hidden (B, S, D), total aux, caches|None)."""
        cfg = self.cfg
        want_cache = ctx.mode == "prefill"
        x = self._embed(params, tokens)
        aux_total = jnp.zeros((), jnp.float32)
        caches: dict = {"prefix": [], "scan": [], "suffix": []}

        for p, s in zip(params["prefix"], self.prefix):
            x, aux, c = _apply_layer_train(p, x, cfg, s, ctx)
            aux_total += aux
            caches["prefix"].append(c)

        if self.repeats:
            unit = self.unit

            def body(carry, layer_params):
                h, aux_in = carry
                aux_here = jnp.zeros((), jnp.float32)
                cs = []
                for i, s in enumerate(unit):
                    h, a, c = _apply_layer_train(layer_params[i], h, cfg,
                                                 s, ctx)
                    aux_here += a
                    cs.append(c)
                ys = cs if want_cache else None
                return (h, aux_in + aux_here), ys

            if ctx.remat:
                # ADSALA_REMAT_POLICY=dots saves matmul outputs so the
                # backward pass recomputes only elementwise ops (§Perf:
                # trades activation memory for ~fwd-worth of FLOPs).
                if os.environ.get("ADSALA_REMAT_POLICY") == "dots":
                    body = jax.checkpoint(
                        body, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                else:
                    body = jax.checkpoint(body)
            # ADSALA_SCAN_UNROLL=full unrolls the layer loop so XLA cost
            # analysis counts every layer (dry-run accounting mode; the
            # default scan keeps HLO small for fast compiles).
            unroll = (self.repeats
                      if os.environ.get("ADSALA_SCAN_UNROLL") == "full"
                      else 1)
            (x, aux_total), scan_caches = jax.lax.scan(
                body, (x, aux_total), params["scan"], unroll=unroll)
            caches["scan"] = scan_caches if want_cache else []

        for p, s in zip(params["suffix"], self.suffix):
            x, aux, c = _apply_layer_train(p, x, cfg, s, ctx)
            aux_total += aux
            caches["suffix"].append(c)

        x = L.apply_norm(params["ln_f"], x, cfg.norm_kind)
        return x, aux_total, caches if want_cache else None

    # -- public entry points ---------------------------------------------------
    def loss(self, params: dict, batch: dict, ctx: Ctx | None = None
             ) -> jax.Array:
        ctx = ctx or Ctx(mode="train")
        x, aux, _ = self.forward(params, batch["tokens"], ctx)
        ce = chunked_cross_entropy(x, self._unembed_weight(params),
                                   batch["labels"])
        return ce + 0.01 * aux

    def logits_last(self, params: dict, x: jax.Array) -> jax.Array:
        return jnp.einsum("bd,dv->bv", x[:, -1],
                          self._unembed_weight(params))

    def init_cache(self, batch: int, ctx: Ctx, dtype=jnp.float32) -> dict:
        cfg = self.cfg
        cache: dict = {
            "prefix": [_init_layer_cache(cfg, s, batch, ctx, dtype)
                       for s in self.prefix],
            "suffix": [_init_layer_cache(cfg, s, batch, ctx, dtype)
                       for s in self.suffix],
        }
        if self.repeats:
            unit_cache = [_init_layer_cache(cfg, s, batch, ctx, dtype)
                          for s in self.unit]
            cache["scan"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (self.repeats,) + a.shape).copy(), unit_cache)
        else:
            cache["scan"] = []
        return cache

    def init_paged_cache(self, n_pages: int, page_size: int, ctx: Ctx,
                         dtype=jnp.float32) -> dict:
        """Page-pool tree mirroring :meth:`init_cache` structure-for-
        structure — PagedKV / PagedLatent pools instead of per-batch
        contiguous caches.  All layers share one page table (they see
        the same token positions), so the scheduler allocates once and
        every layer's pool is indexed by the same physical page ids."""
        cfg = self.cfg
        cache: dict = {
            "prefix": [_init_layer_paged(cfg, s, n_pages, page_size,
                                         ctx, dtype)
                       for s in self.prefix],
            "suffix": [_init_layer_paged(cfg, s, n_pages, page_size,
                                         ctx, dtype)
                       for s in self.suffix],
        }
        if self.repeats:
            unit_cache = [_init_layer_paged(cfg, s, n_pages, page_size,
                                            ctx, dtype)
                          for s in self.unit]
            cache["scan"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (self.repeats,) + a.shape).copy(), unit_cache)
        else:
            cache["scan"] = []
        return cache

    def prefill(self, params: dict, tokens: jax.Array, ctx: Ctx
                ) -> tuple[jax.Array, dict]:
        """Run the full prompt; return (last-token logits, decode caches)."""
        x, _, caches = self.forward(params, tokens, ctx)
        return self.logits_last(params, x), caches

    def decode_step(self, params: dict, token: jax.Array, cache: dict,
                    pos: jax.Array, ctx: Ctx,
                    page_table: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
        """token (B, 1) int32 -> (logits (B, V), new cache).

        With ``page_table`` (B, P) the cache tree holds page pools and
        ``pos`` is (B,) per-sequence positions (-1 = inactive slot) —
        the continuous-batching paged decode (repro.serve.scheduler).
        """
        cfg = self.cfg
        x = self._embed(params, token)

        new_prefix = []
        for p, s, c in zip(params["prefix"], self.prefix, cache["prefix"]):
            x, c2 = _apply_layer_decode(p, x, c, pos, cfg, s, ctx,
                                        page_table)
            new_prefix.append(c2)

        new_scan = cache["scan"]
        if self.repeats:
            unit = self.unit

            def body(h, pc):
                layer_params, layer_cache = pc
                new_caches = []
                for i, s in enumerate(unit):
                    h, c2 = _apply_layer_decode(
                        layer_params[i], h, layer_cache[i], pos, cfg, s,
                        ctx, page_table)
                    new_caches.append(c2)
                return h, new_caches

            x, new_scan = jax.lax.scan(
                body, x, (params["scan"], cache["scan"]))

        new_suffix = []
        for p, s, c in zip(params["suffix"], self.suffix, cache["suffix"]):
            x, c2 = _apply_layer_decode(p, x, c, pos, cfg, s, ctx,
                                        page_table)
            new_suffix.append(c2)

        x = L.apply_norm(params["ln_f"], x, cfg.norm_kind)
        logits = self.logits_last(params, x)
        return logits, {"prefix": new_prefix, "scan": new_scan,
                        "suffix": new_suffix}


def build_lm(cfg: ArchConfig) -> LM:
    return LM(cfg)
