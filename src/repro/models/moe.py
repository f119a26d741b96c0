"""Mixture-of-Experts layer: top-k routing, two dispatch paths.

1. ``apply_moe``    — one-hot einsum dispatch (GShard style).  Dense and
   simple; O(T·E·C·D) dispatch FLOPs make it suitable only for the small
   smoke/test configs.
2. ``apply_moe_ep`` — production expert-parallel path, designed to run
   INSIDE ``shard_map``: per-device sort-based dispatch (gather/scatter,
   zero FLOPs), ``all_to_all`` over the expert axis, grouped GEMM on the
   local experts, ``all_to_all`` back, local combine.  This is the
   TPU-idiomatic translation of GPU MoE kernels (DESIGN.md).

The per-expert GEMMs are the paper's "small & irregular" regime — the
ADSALA tuner's strongest use case: expert bucket rows (~100s) times
d_model, exactly the GEMM sizes where "use every chip" loses badly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import linear
from repro.models.params import ParamDef

__all__ = ["MoESpec", "moe_defs", "apply_moe", "apply_moe_ep",
           "apply_moe_tp"]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    #: mesh axis name carrying expert parallelism in the EP path
    ep_axis: str = "model"

    def capacity(self, n_tokens: int) -> int:
        cap = int(self.capacity_factor * n_tokens * self.top_k
                  / self.n_experts)
        return max(8, -(-cap // 8) * 8)


def moe_defs(s: MoESpec) -> dict:
    e, d, f = s.n_experts, s.d_model, s.d_ff
    # "experts" / "expert_ff" are resolved by the sharding rules: expert-
    # parallel meshes shard the leading dim, expert-TP meshes (n_experts
    # not divisible by the axis) shard the FF dim instead.
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.02),
        "wi": ParamDef((e, d, f), ("experts", "embed", "expert_ff")),
        "wg": ParamDef((e, d, f), ("experts", "embed", "expert_ff")),
        "wo": ParamDef((e, f, d), ("experts", "expert_ff", "embed")),
    }
    if s.n_shared:
        defs["shared_wi"] = ParamDef((d, s.n_shared * f), ("embed", "ff"))
        defs["shared_wg"] = ParamDef((d, s.n_shared * f), ("embed", "ff"))
        defs["shared_wo"] = ParamDef((s.n_shared * f, d), ("ff", "embed"))
    return defs


def _route(p: dict, xf: jax.Array, s: MoESpec
           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(gate_vals, gate_idx, aux_loss) for flat tokens xf (T, D)."""
    logits = linear(xf, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, s.top_k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], s.n_experts, dtype=jnp.float32),
        axis=0)
    aux = s.n_experts * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return gate_vals, gate_idx, aux


def _shared_ffn(p: dict, xf: jax.Array, tuner=None) -> jax.Array:
    ops.observe(xf.shape[0], xf.shape[1],
                2 * p["shared_wi"].shape[-1], tuner,
                site="moe.shared_in")
    ops.observe(xf.shape[0], p["shared_wo"].shape[-2],
                p["shared_wo"].shape[-1], tuner, site="moe.shared_out")
    sh = jax.nn.silu(linear(xf, p["shared_wg"])) * linear(xf, p["shared_wi"])
    return linear(sh, p["shared_wo"])


# ---------------------------------------------------------------------------
# Path 1: dense one-hot dispatch (small configs, pure jit)
# ---------------------------------------------------------------------------

def apply_moe(p: dict, x: jax.Array, s: MoESpec, tuner=None,
              backend: str = "auto"
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  One-hot einsum dispatch."""
    b, sl, d = x.shape
    n_tok = b * sl
    xf = x.reshape(n_tok, d)
    cap = s.capacity(n_tok)
    gate_vals, gate_idx, aux = _route(p, xf, s)

    onehot = jax.nn.one_hot(gate_idx, s.n_experts, dtype=jnp.int32)
    flat = onehot.reshape(n_tok * s.top_k, s.n_experts)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat
    pos = (pos_in_expert * flat).sum(-1).reshape(n_tok, s.top_k)
    keep = pos < cap

    disp_e = onehot.astype(x.dtype)
    disp_c = jax.nn.one_hot(jnp.where(keep, pos, cap), cap, dtype=x.dtype)
    buckets = jnp.einsum("td,tke,tkc->ecd", xf, disp_e, disp_c)

    hi = ops.grouped_matmul(buckets, p["wi"], tuner=tuner,
                            backend=backend, site="moe.wi")
    hg = ops.grouped_matmul(buckets, p["wg"], tuner=tuner,
                            backend=backend, site="moe.wg")
    y = ops.grouped_matmul(jax.nn.silu(hg) * hi, p["wo"], tuner=tuner,
                           backend=backend, site="moe.wo")

    combine = disp_e * (gate_vals * keep).astype(x.dtype)[..., None]
    out = jnp.einsum("ecd,tke,tkc->td", y, combine, disp_c)
    if s.n_shared:
        out = out + _shared_ffn(p, xf, tuner)
    return out.reshape(b, sl, d), aux


# ---------------------------------------------------------------------------
# Path 2: expert-parallel sort-based dispatch (inside shard_map)
# ---------------------------------------------------------------------------

def _dispatch(xf: jax.Array, gate_idx: jax.Array, s: MoESpec, cap: int):
    """Sort-based bucket build: gathers/scatters only, zero FLOPs.

    Returns (buckets (E, cap, D), dest (T*k,), order, valid) where dest
    maps each sorted (token, choice) to its bucket row.
    """
    n_tok = xf.shape[0]
    flat_expert = gate_idx.reshape(-1)                     # (T*k,)
    order = jnp.argsort(flat_expert)                       # stable
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=s.n_experts)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n_tok * s.top_k) - starts[sorted_expert]
    token_of = order // s.top_k
    valid = rank < cap
    dest = jnp.where(valid, sorted_expert * cap + rank, s.n_experts * cap)
    buckets = jnp.zeros((s.n_experts * cap + 1, xf.shape[1]), xf.dtype)
    buckets = buckets.at[dest].set(xf[token_of], mode="drop",
                                   unique_indices=True)
    return buckets[:-1].reshape(s.n_experts, cap, -1), dest, order, valid


def _combine(y: jax.Array, dest: jax.Array, order: jax.Array,
             valid: jax.Array, gate_vals: jax.Array, n_tok: int,
             s: MoESpec) -> jax.Array:
    d = y.shape[-1]
    yf = jnp.concatenate(
        [y.reshape(s.n_experts * y.shape[1], d),
         jnp.zeros((1, d), y.dtype)], axis=0)
    per_choice = yf[dest]                                  # (T*k, D) sorted
    unsort = jnp.argsort(order)
    per_choice = per_choice[unsort].reshape(n_tok, s.top_k, d)
    keep = (valid[unsort]).reshape(n_tok, s.top_k)
    w = (gate_vals * keep).astype(y.dtype)
    return jnp.einsum("tkd,tk->td", per_choice, w)


def apply_moe_ep(p: dict, x: jax.Array, s: MoESpec, tuner=None,
                 backend: str = "auto"
                 ) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE (n_experts divisible by the ep axis).

    MUST run inside shard_map with ``x`` a per-device shard (B_loc,
    S_loc, D), expert weights sharded on their leading dim over
    ``s.ep_axis``, the router replicated.

    Steps: local top-k route -> sort-based bucket build -> all_to_all
    (experts) -> grouped GEMM -> all_to_all back -> combine.
    """
    b, sl, d = x.shape
    n_tok = b * sl
    xf = x.reshape(n_tok, d)
    cap = s.capacity(n_tok)
    gate_vals, gate_idx, aux = _route(p, xf, s)
    aux = jax.lax.pmean(aux, s.ep_axis)

    buckets, dest, order, valid = _dispatch(xf, gate_idx, s, cap)

    # (E, C, D) -> (E/ep, ep*C, D): rows for my local experts from all peers
    buckets = jax.lax.all_to_all(buckets, s.ep_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
    hi = ops.grouped_matmul(buckets, p["wi"], tuner=tuner,
                            backend=backend, site="moe.wi")
    hg = ops.grouped_matmul(buckets, p["wg"], tuner=tuner,
                            backend=backend, site="moe.wg")
    y = ops.grouped_matmul(jax.nn.silu(hg) * hi, p["wo"], tuner=tuner,
                           backend=backend, site="moe.wo")
    y = jax.lax.all_to_all(y, s.ep_axis, split_axis=1, concat_axis=0,
                           tiled=True)                     # (E, C, D)

    out = _combine(y, dest, order, valid, gate_vals, n_tok, s)
    if s.n_shared:
        out = out + _shared_ffn(p, xf, tuner)
    return out.reshape(b, sl, d), aux


def apply_moe_tp(p: dict, x: jax.Array, s: MoESpec, tuner=None,
                 backend: str = "auto"
                 ) -> tuple[jax.Array, jax.Array]:
    """Expert-TP MoE for small expert counts (mixtral: 8 experts on a
    16-way model axis).  MUST run inside shard_map with ``x`` replicated
    over the tp axis (tokens sharded over data axes only) and expert
    weights sharded on the FF dim (wi/wg last dim, wo middle dim).

    Every tp member computes all experts on its FF slice; a single psum
    over the tp axis rebuilds the expert outputs — the standard
    Megatron-style tensor parallelism applied per expert.
    """
    b, sl, d = x.shape
    n_tok = b * sl
    xf = x.reshape(n_tok, d)
    cap = s.capacity(n_tok)
    gate_vals, gate_idx, aux = _route(p, xf, s)
    aux = jax.lax.pmean(aux, s.ep_axis)

    buckets, dest, order, valid = _dispatch(xf, gate_idx, s, cap)
    hi = ops.grouped_matmul(buckets, p["wi"], tuner=tuner,
                            backend=backend, site="moe.wi")  # (E, C, F/tp)
    hg = ops.grouped_matmul(buckets, p["wg"], tuner=tuner,
                            backend=backend, site="moe.wg")
    y = ops.grouped_matmul(jax.nn.silu(hg) * hi, p["wo"], tuner=tuner,
                           backend=backend, site="moe.wo")  # partial sums
    y = jax.lax.psum(y, s.ep_axis)

    out = _combine(y, dest, order, valid, gate_vals, n_tok, s)
    if s.n_shared:
        out = out + _shared_ffn(p, xf, tuner)
    return out.reshape(b, sl, d), aux
