"""Weights from the seed, the same for the program and the reference.

Every tensor of a dense decoder has a plain name (``embed``, ``q``,
``down``, ``attn_norm.scale``, ...) and is drawn from the seed, its name
and its layer alone, so the reference can draw one layer at a time what
the program is given all at once. :func:`program_params` lays the same
tensors out as the program's parameter tree, in one jitted call on the
device, in the configuration's serving dtype.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "layer_specs", "global_specs", "draw",
           "draw_layer", "program_params"]


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key for any whole number."""
    state = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(state, jnp.uint32)


def global_specs(k: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, init) of the tensors outside the layers."""
    d, v = k["d"], k["vocab"]
    out = {"embed": ((v, d), "unit"),
           "final_norm.scale": ((d,), "scale")}
    if k["norm"] == "layernorm":
        out["final_norm.bias"] = ((d,), "bias")
    if not k["tied"]:
        out["unembed"] = ((d, v), "fan_in")
    return out


def layer_specs(k: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, init) of one layer's tensors."""
    d, ff, hd = k["d"], k["ff"], k["head_dim"]
    out = {"attn_norm.scale": ((d,), "scale"),
           "q": ((d, k["heads"] * hd), "fan_in"),
           "k": ((d, k["kv_heads"] * hd), "fan_in"),
           "v": ((d, k["kv_heads"] * hd), "fan_in"),
           "o": ((k["heads"] * hd, d), "fan_in"),
           "mlp_norm.scale": ((d,), "scale"),
           "gate": ((d, ff), "fan_in"),
           "up": ((d, ff), "fan_in"),
           "down": ((ff, d), "fan_in")}
    if k["norm"] == "layernorm":
        out["attn_norm.bias"] = ((d,), "bias")
        out["mlp_norm.bias"] = ((d,), "bias")
    return out


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


def draw_layer(key: jax.Array, k: dict, layer, dtype) -> dict:
    return {n: draw(key, n, s, i, layer, dtype)
            for n, (s, i) in layer_specs(k).items()}


def _norm(p: dict, prefix: str, k: dict) -> dict:
    out = {"scale": p[f"{prefix}.scale"]}
    if k["norm"] == "layernorm":
        out["bias"] = p[f"{prefix}.bias"]
    return out


def program_params(model, k: dict, seed: int, dtype) -> dict:
    """The program's parameter tree, drawn on the device in one call.

    Checks the tree against the program's own ``model.init`` layout
    (structure, shapes) so a change of layout fails here, not as a wrong
    answer."""
    if model.prefix or model.suffix or len(model.unit) != 1:
        raise ValueError("the harness lays out decoders whose layers all "
                         "repeat one attention + MLP unit")
    n_layers = model.repeats

    def build(key):
        g = {n: draw(key, n, s, i, 0, dtype)
             for n, (s, i) in global_specs(k).items()}
        layers = jax.vmap(lambda l: draw_layer(key, k, l, dtype))(
            jnp.arange(n_layers))
        unit = {"ln1": _norm(layers, "attn_norm", k),
                "mixer": {"wq": layers["q"], "wk": layers["k"],
                          "wv": layers["v"], "wo": layers["o"]},
                "ln2": _norm(layers, "mlp_norm", k),
                "mlp": {"wi": layers["up"], "wg": layers["gate"],
                        "wo": layers["down"]}}
        tree = {"embed": g["embed"], "ln_f": _norm(g, "final_norm", k),
                "prefix": [], "scan": [unit], "suffix": []}
        if not k["tied"]:
            tree["unembed"] = g["unembed"]
        return tree

    key = seed_key(seed)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    got = jax.eval_shape(build, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.jit(build)(key)
