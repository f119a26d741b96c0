"""What every plain reference shares: float32 products at the highest
precision, the fp8 rounding of the control, the padding that gives one
compile per cell, and the gap of a served token below the reference's
best.

A family's reference (``reference/<family>.py``) gives ``logits(tokens,
fp8=False, length=0)``: float32 logits at every position of ``tokens``,
padded to a multiple of :data:`PAD` of at least ``length``; with
``fp8=True`` the control's, in the precision below the served bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HI", "PAD", "CHUNK", "to_fp8", "mm", "served_gaps"]

HI = jax.lax.Precision.HIGHEST

#: padded sequence lengths are multiples of this; a caller that passes
#: ``length`` (the cell's ``max_seq_len``) gets one compile for all
PAD = 256

#: queries per attention block
CHUNK = 512


def to_fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 under an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        x, w = to_fp8(x, -1), to_fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


@jax.jit
def _gap(ref: jax.Array, tokens: jax.Array) -> jax.Array:
    """How far each position's token lies below the reference's best."""
    pick = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return ref.max(axis=1) - pick


def served_gaps(ref, prompt: np.ndarray, served: np.ndarray,
                control: bool = False, length: int = 0) -> dict:
    """The gap of every served token (and, with ``control``, of the token
    the fp8 control puts first at the same position), as numpy arrays
    over the served positions."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    inp, nxt = seq[:-1], seq[1:]
    lo, hi = len(prompt) - 1, len(inp)
    out = {}
    if control:
        pick = jnp.argmax(ref.logits(inp, fp8=True, length=length), axis=1)
    r = ref.logits(inp, length=length)
    pad = np.zeros(r.shape[0], np.int32)
    pad[: len(nxt)] = nxt
    out["served"] = np.asarray(_gap(r, jnp.asarray(pad)))[lo:hi]
    if control:
        out["control"] = np.asarray(_gap(r, pick.astype(jnp.int32)))[lo:hi]
    return out
