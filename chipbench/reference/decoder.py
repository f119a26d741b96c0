"""Plain float32 reference of the dense decoders the benchmark serves.

Pre-norm decoder layers: norm (LayerNorm with bias, or RMSNorm), q/k/v
projections, rotary embedding on the first ``partial_rotary_factor`` of
each head (halves rotated), causal softmax attention with each group of
``heads / kv_heads`` query heads sharing a key/value head, the output
projection, a residual; norm, a SiLU-gated MLP, a residual. A final norm
and the output head. Every product runs in float32 at the highest
precision. No kernel, cache or batch: one sequence, all its positions,
one layer at a time, the layer's weights drawn from the seed as it is
reached (:mod:`weights`), so the whole model never has to fit at once.
The tensors are those ``families/dense.py`` names.

``fp8=True`` is the control: the same mathematics with every product's
operands (weights, activations, queries, keys and values) rounded to
float8 e4m3 under an absmax scale per row or column, the precision below
the bfloat16 the configurations serve in.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from reference.common import CHUNK, HI, PAD, mm, to_fp8

__all__ = ["Reference"]


def _norm(x: jax.Array, p: dict, prefix: str, k: dict) -> jax.Array:
    if k["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + k["norm_eps"])
                * p[f"{prefix}.scale"] + p[f"{prefix}.bias"])
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + k["norm_eps"]) * p[f"{prefix}.scale"]


def _rope(x: jax.Array, k: dict) -> jax.Array:
    """x (L, H, hd): rotate the first ``rotary`` share of each head."""
    hd = x.shape[-1]
    rot = int(hd * k["rotary"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / k["rope_theta"] ** (np.arange(0, rot, 2) / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(q, kk, v, k: dict, fp8: bool) -> jax.Array:
    """Causal attention; q (L, H, hd), kk/v (L, Hkv, hd) -> (L, H*hd)."""
    n, h, hd = q.shape
    g = k["kv_heads"]
    if fp8:
        q, kk, v = to_fp8(q, -1), to_fp8(kk, -1), to_fp8(v, -1)
    q = q.reshape(n, g, h // g, hd)
    outs = []
    for s in range(0, n, CHUNK):
        qc = q[s: s + CHUNK]
        sc = jnp.einsum("qgrd,kgd->grqk", qc, kk, precision=HI) * hd ** -0.5
        qi = s + jnp.arange(qc.shape[0])[:, None]
        sc = jnp.where(jnp.arange(n)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=0).reshape(n, h * hd)


def _layer(x: jax.Array, p: dict, k: dict, fp8: bool) -> jax.Array:
    n = x.shape[0]
    hd = k["head_dim"]
    h = _norm(x, p, "attn_norm", k)
    q = _rope(mm(h, p["q"], fp8).reshape(n, k["heads"], hd), k)
    kk = _rope(mm(h, p["k"], fp8).reshape(n, k["kv_heads"], hd), k)
    v = mm(h, p["v"], fp8).reshape(n, k["kv_heads"], hd)
    x = x + mm(_attention(q, kk, v, k, fp8), p["o"], fp8)
    h = _norm(x, p, "mlp_norm", k)
    return x + mm(jax.nn.silu(mm(h, p["gate"], fp8)) * mm(h, p["up"], fp8),
                  p["down"], fp8)


class Reference:
    """The reference model of one configuration and one seed.

    It draws the tensors its family names: the family's subclass sets
    ``global_specs`` and ``layer_specs`` (``k -> {name: (shape, init)}``),
    the specs it also lays out as the program's parameters."""

    global_specs: Callable[[dict], dict]
    layer_specs: Callable[[dict], dict]

    def __init__(self, k: dict, seed: int, dtype=jnp.bfloat16) -> None:
        self.k = k
        self.key = W.seed_key(seed)
        g = self.global_specs(k)
        per_layer = self.layer_specs(k)

        def tensor(key, name, layer=0):
            shape, init = (g | per_layer)[name]
            return W.draw(key, name, shape, init, layer,
                          dtype).astype(jnp.float32)

        @jax.jit
        def embed(key, tokens):
            return tensor(key, "embed")[tokens]

        @functools.partial(jax.jit, static_argnames="fp8")
        def layer(key, x, l, fp8):
            p = {n: W.draw(key, n, s, i, l, dtype).astype(jnp.float32)
                 for n, (s, i) in per_layer.items()}
            return _layer(x, p, k, fp8)

        @functools.partial(jax.jit, static_argnames="fp8")
        def head(key, x, fp8):
            p = {n: tensor(key, n) for n in g if n != "embed"}
            w = (tensor(key, "embed").T if k["tied"] else p["unembed"])
            return mm(_norm(x, p, "final_norm", k), w, fp8)

        self._embed, self._layer, self._head = embed, layer, head

    def logits(self, tokens: np.ndarray, fp8: bool = False,
               length: int = 0) -> jax.Array:
        """(Lp, vocab) float32 logits at every position of ``tokens``,
        padded to a multiple of :data:`PAD` of at least ``length`` (the
        padding only follows)."""
        n = len(tokens)
        lp = -(-max(n, length) // PAD) * PAD
        t = np.zeros(lp, np.int32)
        t[:n] = tokens
        x = self._embed(self.key, jnp.asarray(t))
        for l in range(self.k["layers"]):
            x = self._layer(self.key, x, l, fp8)
        return self._head(self.key, x, fp8)
