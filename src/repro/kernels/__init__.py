"""Pallas TPU kernels (+ jnp oracles) for the perf-critical GEMM paths.

matmul          — tiled MXU matmul, tile = ADSALA worker-config axis
grouped_matmul  — expert-batched MoE GEMM over capacity buckets
flash_attention — online-softmax blocked attention (causal / windowed)
paged_attention — single-token decode attention over a paged KV pool,
                  reading only live pages
recorder        — DispatchRecorder: observe (routine, m, k, n, config,
                  cache_hit) per dispatch on the current thread
"""

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ops import (
    dispatch_hint,
    flash_attention,
    grouped_dispatch_hint,
    grouped_matmul,
    matmul,
    observe,
    resolve_backend,
    resolve_interpret,
    supported_routine,
    syrk,
    trsm,
)
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.recorder import DispatchEvent, DispatchRecorder
from repro.kernels.ref import (
    flash_attention_ref,
    grouped_matmul_ref,
    matmul_ref,
    syrk_ref,
    trsm_ref,
)

__all__ = [
    "matmul_pallas", "grouped_matmul_pallas", "flash_attention_pallas",
    "paged_decode_attention_pallas",
    "matmul", "syrk", "trsm", "grouped_matmul", "flash_attention",
    "dispatch_hint", "grouped_dispatch_hint", "observe",
    "resolve_backend", "resolve_interpret", "supported_routine",
    "DispatchEvent", "DispatchRecorder",
    "matmul_ref", "syrk_ref", "trsm_ref", "grouped_matmul_ref",
    "flash_attention_ref",
]
