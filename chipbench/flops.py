"""Operations and bytes the served work needs, from the configuration.

Counts are of the model's mathematics at the live context: what the
request needs, not what a kernel happens to compute (no padding, no
gathered cap, no masked tiles). A multiply-add is 2 operations.
"""

from __future__ import annotations

__all__ = ["dense_per_token", "decode_flops", "prefill_flops",
           "flash_flops", "flash_bytes", "roofline_s", "load_peaks"]


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


def flash_flops(bh: int, s: int, head_dim: int) -> float:
    """One causal self-attention call over ``bh`` heads of ``s`` tokens:
    the lower triangle with its diagonal, whatever grid runs it."""
    return 4.0 * head_dim * bh * s * (s + 1) / 2


def flash_bytes(bh: int, s: int, head_dim: int, itemsize: int) -> float:
    """q, k and v read once, the output written once."""
    return 4.0 * bh * s * head_dim * itemsize


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an
    error."""
    import json
    from pathlib import Path

    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]
