"""Weights from the seed, the same for the program and the reference.

Every tensor has a plain name (``embed``, ``q``, ``attn_norm.scale``,
...) and is drawn from the seed, its name and its layer alone, so the
reference can draw one layer at a time what the program is given all at
once. A family (``families/``) names its tensors and lays the same ones
out as the program's parameter tree, in one jitted call on the device,
in the configuration's serving dtype.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "draw"]


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key for any whole number."""
    state = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(state, jnp.uint32)


def draw(key: jax.Array, name: str, shape: tuple[int, ...], init: str,
         layer, dtype) -> jax.Array:
    """One tensor: a function of (seed, name, layer) only."""
    kk = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(name.encode()) & 0x7FFFFFFF), layer)
    z = jax.random.normal(kk, shape, jnp.float32)
    if init == "unit":
        x = z
    elif init == "fan_in":
        x = z * shape[0] ** -0.5
    elif init == "scale":
        x = 1.0 + 0.1 * z
    elif init == "bias":
        x = 0.1 * z
    else:
        raise ValueError(init)
    return x.astype(dtype)
