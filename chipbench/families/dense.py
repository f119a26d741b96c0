"""The dense decoder family: stablelm-1.6b, granite-8b.

Pre-norm decoder layers that all repeat one unit: attention with each
group of ``num_attention_heads / num_key_value_heads`` query heads
sharing a key/value head (MHA where they are equal), rotary on the first
``partial_rotary_factor`` of each head, LayerNorm or RMSNorm, and a
SiLU-gated MLP; configuration keys as Hugging Face names them for such
models. The plain reference is ``reference/decoder.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import decoder
from weights import draw, seed_key

__all__ = ["dims", "program_config", "global_specs", "layer_specs",
           "draw_layer", "program_params", "Reference", "dense_per_token",
           "decode_flops", "prefill_flops", "smoke"]


def dims(config: dict) -> dict:
    """The sizes the harness computes with, from a configuration file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "d": d,
        "ff": int(config["intermediate_size"]),
        "heads": h,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // h),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "rotary": float(config.get("partial_rotary_factor", 1.0)),
        "rope_theta": float(config["rope_theta"]),
        "norm": "layernorm" if "layer_norm_eps" in config else "rmsnorm",
        "norm_eps": float(config.get("layer_norm_eps",
                                     config.get("rms_norm_eps"))),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def program_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: the
    program's own entry for ``program_arch`` with the file's sizes, and a
    check that everything else the program fixes agrees with the file."""
    import dataclasses as dc

    from repro.configs import get_config

    k = dims(config)
    arch = dc.replace(
        get_config(config["program_arch"]), n_layers=k["layers"],
        d_model=k["d"], n_heads=k["heads"], n_kv_heads=k["kv_heads"],
        d_ff=k["ff"], vocab=k["vocab"], head_dim=None)
    want = {"resolved_head_dim": k["head_dim"],
            "rope_fraction": k["rotary"], "norm_kind": k["norm"],
            "tie_embeddings": k["tied"], "mlp_kind": "swiglu",
            "attn_kind": "gqa", "window": None, "qk_norm": False}
    got = {key: getattr(arch, key) for key in want}
    if got != want:
        raise ValueError(f"{config['name']}: the program's configuration "
                         f"{got} departs from the file's {want}")
    return arch


# ---------------------------------------------------------------- weights
# Every tensor has a plain name (``embed``, ``q``, ``down``,
# ``attn_norm.scale``, ...) and is drawn from the seed, its name and its
# layer alone (``weights.draw``), so the reference can draw one layer at
# a time what the program is given all at once.


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


class Reference(decoder.Reference):
    """The plain float32 reference, drawing the tensors named above."""

    global_specs = staticmethod(global_specs)
    layer_specs = staticmethod(layer_specs)


# --------------------------------------------------------------- operations
# Counts of the model's mathematics at the live context (flops.py).


def dense_per_token(k: dict) -> float:
    """Projections and MLP of every layer, per token (no attention
    scores, no logits)."""
    d, hd = k["d"], k["head_dim"]
    proj = 2 * d * hd * (k["heads"] + 2 * k["kv_heads"]) \
        + 2 * k["heads"] * hd * d
    mlp = 3 * 2 * d * k["ff"]
    return k["layers"] * (proj + mlp)


def _attn_per_key(k: dict) -> float:
    """Scores and weighted values, per (query, key) pair, all layers."""
    return k["layers"] * 4 * k["heads"] * k["head_dim"]


def _logits(k: dict) -> float:
    return 2 * k["d"] * k["vocab"]


def decode_flops(k: dict, ctx: int) -> float:
    """One decoded token that attends over ``ctx`` keys (itself
    included), with its logits."""
    return dense_per_token(k) + _attn_per_key(k) * ctx + _logits(k)


def prefill_flops(k: dict, n: int) -> float:
    """A prompt of ``n`` tokens under a causal mask, with the logits of
    its last token."""
    return (n * dense_per_token(k) + _attn_per_key(k) * n * (n + 1) / 2
            + _logits(k))


def smoke(config: dict) -> dict:
    """The keys that cut a configuration to a size the CPU runs in
    seconds: every width cut, depth 2, and the configuration's query
    heads per key/value head kept where four query heads allow it."""
    kv = config["num_key_value_heads"] * 4 // config["num_attention_heads"]
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "vocab_size": 256, "num_key_value_heads": max(1, kv)}
