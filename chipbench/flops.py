"""Operations and bytes the served work needs, from the configuration.

Counts are of the model's mathematics at the live context: what the
request needs, not what a kernel happens to compute (no padding, no
gathered cap, no masked tiles). A multiply-add is 2 operations. A
model's own counts, ``decode_flops`` and ``prefill_flops``, are its
family's (``families/``); here are the kernels' and the chip's.
"""

from __future__ import annotations

__all__ = ["flash_flops", "flash_bytes", "roofline_s", "load_peaks"]


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
