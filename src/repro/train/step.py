"""Train-step builder: loss -> grad -> AdamW, mesh-aware.

``build_train_step`` returns (step_fn, state_specs, batch_specs) so the
launcher/dry-run can jit with explicit in/out shardings and lower against
ShapeDtypeStructs without allocating anything.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import batch_specs, partition_params, state_specs
from repro.kernels import recorder
from repro.models.config import ArchConfig, ShapeSpec
from repro.models.transformer import make_ctx
from repro.train.optim import (
    STATE_MOMENTS,
    AdamWConfig,
    adamw_update,
    init_state,
)

__all__ = ["build_train_step", "make_ctx", "abstract_state",
           "train_batch_sds"]


def abstract_state(model, cfg: ArchConfig, opt_cfg: AdamWConfig,
                   dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct train state (no allocation) for .lower()."""
    from repro.models.params import abstract_params
    p = abstract_params(model.defs, dtype)
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p)
    state = {"params": p}
    for key in STATE_MOMENTS:
        state[key] = f32
    state["step"] = jax.ShapeDtypeStruct((), jnp.int32)
    if opt_cfg.compress:
        state["ef"] = f32
    return state


def train_batch_sds(cfg: ArchConfig, shape: ShapeSpec,
                    dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct stand-ins for one global training batch."""
    b, s = shape.global_batch, shape.seq_len
    sds = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
           "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.family == "audio":
        sds["audio_emb"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_len, cfg.d_model), dtype)
    return sds


def build_train_step(model, cfg: ArchConfig, shape: ShapeSpec, mesh,
                     opt_cfg: AdamWConfig | None = None, tuner=None):
    """Returns (train_step, state_spec_tree, batch_spec_tree).

    ``tuner`` is threaded to every routine-aware call site via the Ctx;
    the step also tags the backward-pass contractions: for each forward
    event the recorder collected while the loss traced, the two
    AD-transposed gemm shapes (dX, dW) are recorded, so a recorded
    train step shows forward *and* backward dispatch volume.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    ctx = make_ctx(mesh, "train", tuner=tuner)

    def loss_fn(params, batch):
        return model.loss(params, batch, ctx)

    def train_step(state, batch):
        n0 = recorder.active_event_count()
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        recorder.record_backward(since=n0, tuner=tuner)
        new_state, metrics = adamw_update(state, grads, opt_cfg)
        metrics["loss"] = loss
        return new_state, metrics

    if mesh is None:
        return train_step, None, None
    p_specs = partition_params(model, cfg, mesh)
    s_specs = state_specs(p_specs, compress=opt_cfg.compress)
    b_specs = batch_specs(cfg, shape, mesh)
    return train_step, s_specs, b_specs


def init_train_state(model, cfg: ArchConfig, opt_cfg: AdamWConfig,
                     rng, dtype=jnp.float32) -> dict:
    return init_state(model.init(rng, dtype), opt_cfg)
