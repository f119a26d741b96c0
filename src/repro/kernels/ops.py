"""Public jit'd kernel wrappers + ADSALA tuner integration.

``matmul`` / ``grouped_matmul`` / ``flash_attention`` are the entry
points the model layers call.  Backend selection:

  * ``pallas``  — the Pallas TPU kernels, compiled by Mosaic on a TPU
    and run by the Pallas interpreter when JAX's platform is the CPU
    (see :func:`resolve_interpret`; the correctness tests run there);
  * ``xla``     — jnp reference implementations.  The default on CPU
    hosts and inside the multi-pod dry-run, where XLA's SPMD partitioner
    handles the sharded einsums and Mosaic kernels cannot lower.

When an :class:`~repro.core.tuner.AdsalaTuner` is supplied, the call's
(routine, m, k, n) is looked up per call (memoised inside the tuner) and
the chosen worker configuration supplies the kernel tile; the chosen
chip count / partition axis is exposed via :func:`dispatch_hint` for the
distribution layer to turn into sharding constraints.

Every routine-aware entry point also reports its dispatch — the
*resolved* routine, shape, chosen config and whether the tuner served
it from cache — to any active
:class:`~repro.kernels.recorder.DispatchRecorder`.  Routine names are
validated here at the ops boundary (unknown strings fail loudly), and a
routine the tuner's artifact carries no training signal for degrades to
the explicit :data:`~repro.core.costmodel.DEFAULT_ROUTINE` gemm
fallback instead of raising — a v1 gemm-only artifact keeps serving
models whose call sites are routine-tagged.
"""

from __future__ import annotations

import os
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (
    DEFAULT_ROUTINE,
    DEFAULT_TILES,
    ROUTINES,
    GemmConfig,
)
from repro.core.tuner import AdsalaTuner
from repro.kernels import recorder, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.matmul import matmul_pallas

__all__ = ["matmul", "syrk", "trsm", "grouped_matmul", "flash_attention",
           "dispatch_hint", "grouped_dispatch_hint", "observe",
           "resolve_backend", "resolve_interpret", "supported_routine"]

Backend = Literal["auto", "pallas", "xla"]

_BACKENDS = ("auto", "pallas", "xla")


def resolve_backend(backend: Backend = "auto") -> str:
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {_BACKENDS}")
    if backend != "auto":
        return backend
    env = os.environ.get("ADSALA_BACKEND")
    if env:
        if env not in ("pallas", "xla"):
            raise ValueError(
                f"ADSALA_BACKEND={env!r}; expected 'pallas' or 'xla'")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the Pallas interpreter.

    An explicit ``interpret`` wins.  Left as ``None``, interpret mode is
    on only when JAX's platform is the CPU, where Mosaic cannot compile
    and the interpreter stands in for the chip.  On a TPU it is off, so
    the kernels always compile there; on any other platform it is off
    too, and the kernel fails to lower instead of silently running the
    interpreter.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def supported_routine(routine: str, tuner: AdsalaTuner | None) -> str:
    """The routine a call site can actually dispatch.

    Validates the name against :data:`ROUTINES` (unknown strings raise
    here, at the ops boundary, with the full expected set), then falls
    back to the explicit gemm :data:`DEFAULT_ROUTINE` when the tuner's
    artifact was installed without ``routine`` — legacy/v1 artifacts
    and subset installs keep serving instead of raising from deep
    inside a model layer.
    """
    if routine not in ROUTINES:
        raise ValueError(
            f"unknown routine {routine!r}; expected one of {ROUTINES}")
    if tuner is not None and routine not in tuner.routines:
        return DEFAULT_ROUTINE
    return routine


def _select(m: int, k: int, n: int, routine: str,
            tuner: AdsalaTuner | None, *, need_config: bool
            ) -> tuple[str, GemmConfig | None, bool]:
    """(resolved routine, tuner config | None, cache_hit) for one call.

    The tuner is consulted when the kernel needs a tile
    (``need_config``) or a recorder wants the chosen config on the
    event; otherwise (xla path, nobody watching) the lookup is skipped
    so untuned dispatch stays free.
    """
    routine = supported_routine(routine, tuner)
    if tuner is None or not (need_config or recorder.active()):
        return routine, None, False
    hit = tuner.peek(m, k, n, routine)
    return routine, tuner.select(m, k, n, routine), hit


def dispatch_hint(m: int, k: int, n: int,
                  tuner: AdsalaTuner | None,
                  routine: str = DEFAULT_ROUTINE,
                  site: str = "", count: int = 1) -> GemmConfig | None:
    """Worker configuration the tuner recommends for this call (or None).

    Doubles as the observability point for contractions that don't go
    through an ops kernel (einsum call sites in the model layers): the
    resolved routine identity is reported to any active
    DispatchRecorder, with the gemm fallback applied when the artifact
    has no signal for ``routine``.
    """
    routine = supported_routine(routine, tuner)
    cfg, hit = None, False
    if tuner is not None:
        hit = tuner.peek(m, k, n, routine)
        cfg = tuner.select(m, k, n, routine)
    recorder.record(routine, m, k, n, config=cfg, cache_hit=hit,
                    site=site, count=count)
    return cfg


def observe(m: int, k: int, n: int,
            tuner: AdsalaTuner | None,
            routine: str = DEFAULT_ROUTINE,
            site: str = "", count: int = 1) -> None:
    """Observability-only twin of :func:`dispatch_hint`.

    The model-layer einsum call sites discard the hint — they only
    exist so a recorder can see the contraction's routine identity.
    Unlike ``dispatch_hint`` (whose contract is to *return* the tuner's
    recommendation), this consults the tuner only while a recorder is
    active, so eager untuned/unwatched dispatch pays nothing beyond the
    routine-name validation and the tuner's LRU never fills with fused
    hint shapes that are not real kernel dispatches.
    """
    if not recorder.active():
        supported_routine(routine, tuner)   # still fail loudly on typos
        return
    dispatch_hint(m, k, n, tuner, routine, site, count)


def grouped_dispatch_hint(shapes: list[tuple[int, int, int]],
                          tuner: AdsalaTuner | None, *,
                          n_experts: int | None = None,
                          routine: str = DEFAULT_ROUTINE,
                          site: str = "grouped"
                          ) -> list[GemmConfig] | None:
    """Per-expert worker configurations for a grouped (MoE) dispatch.

    All expert GEMMs go through ONE batched tuner lookup
    (:meth:`AdsalaTuner.select_many`) instead of per-expert scalar calls.
    ``n_experts`` (when known) guards against a shape list covering only
    a prefix of the experts — a silent truncation would hand later
    experts no hint at all.  One event per expert shape is reported to
    any active recorder.
    """
    shapes = list(shapes)
    if n_experts is not None and len(shapes) != n_experts:
        raise ValueError(
            f"grouped dispatch got {len(shapes)} GEMM shapes for "
            f"{n_experts} experts; every expert needs a shape")
    routine = supported_routine(routine, tuner)
    cfgs = None
    if tuner is not None:
        hits = [tuner.peek(m, k, n, routine) for m, k, n in shapes]
        cfgs = tuner.select_many(shapes, routines=routine)
    else:
        hits = [False] * len(shapes)
    if recorder.active():
        for (m, k, n), hit, cfg in zip(
                shapes, hits, cfgs or [None] * len(shapes)):
            recorder.record(routine, m, k, n, config=cfg, cache_hit=hit,
                            site=site)
    return cfgs


def matmul(a: jax.Array, b: jax.Array, *,
           tuner: AdsalaTuner | None = None,
           tile: tuple[int, int, int] | None = None,
           backend: Backend = "auto",
           interpret: bool | None = None,
           site: str = "", count: int = 1) -> jax.Array:
    be = resolve_backend(backend)
    m, k, n = int(a.shape[0]), int(a.shape[1]), int(b.shape[1])
    # an explicit tile overrides the tuner entirely: don't consult it,
    # and don't label the event with a config that was never dispatched
    rt, cfg, hit = _select(m, k, n, DEFAULT_ROUTINE,
                           tuner if tile is None else None,
                           need_config=be != "xla")
    recorder.record(rt, m, k, n, config=cfg, cache_hit=hit, site=site,
                    count=count)
    if be == "xla":
        return ref.matmul_ref(a, b)
    bm, bk, bn = (tile if tile is not None
                  else cfg.tile if cfg is not None else DEFAULT_TILES[3])
    return matmul_pallas(a, b, bm=bm, bk=bk, bn=bn,
                         interpret=resolve_interpret(interpret))


def syrk(a: jax.Array, b: jax.Array | None = None, *,
         tuner: AdsalaTuner | None = None,
         tile: tuple[int, int, int] | None = None,
         lower: bool = True,
         backend: Backend = "auto",
         interpret: bool | None = None,
         site: str = "", count: int = 1) -> jax.Array:
    """Symmetric rank-k update C = tril/triu(A @ Aᵀ), A of shape (m, k).

    With ``b`` (same shape as A) this is the SYRK-*shaped* product
    C = tril/triu(A @ Bᵀ): only one triangle of the square output is
    produced, so it prices — and dispatches — as SYRK even though the
    operands differ.  Causal self-attention scores (QKᵀ consumed under
    a triangular mask) are the serving-path instance.

    The Pallas path reuses the tuned matmul kernel and masks the output
    to the written triangle (the kernel computes both halves; the
    analytic cost model charges only the triangular fraction, which is
    what a production SYRK kernel would execute).  Tuner lookups use
    routine="syrk" on the (m, k, m) shape, degrading to gemm on
    artifacts without syrk signal.
    """
    if a.ndim != 2:
        raise ValueError(f"bad SYRK operand shape {a.shape}")
    if b is not None and b.shape != a.shape:
        raise ValueError(
            f"bad SYRK-shaped operands {a.shape} x {b.shape}; B must "
            "match A (square output, shared k)")
    m, k = int(a.shape[0]), int(a.shape[1])
    be = resolve_backend(backend)
    rt, cfg, hit = _select(m, k, m, "syrk",
                           tuner if tile is None else None,
                           need_config=be != "xla")
    recorder.record(rt, m, k, m, config=cfg, cache_hit=hit, site=site,
                    count=count)
    if be == "xla":
        return ref.syrk_ref(a, b, lower=lower)
    bm, bk, bn = (tile if tile is not None
                  else cfg.tile if cfg is not None else DEFAULT_TILES[3])
    c = matmul_pallas(a, (a if b is None else b).T, bm=bm, bk=bk, bn=bn,
                      interpret=resolve_interpret(interpret),
                      out_dtype=jnp.float32)
    c = jnp.tril(c) if lower else jnp.triu(c)
    return c.astype(a.dtype)


def trsm(a: jax.Array, b: jax.Array, *,
         tuner: AdsalaTuner | None = None,
         tile: tuple[int, int, int] | None = None,
         lower: bool = True,
         unit_diag: bool = False,
         backend: Backend = "auto",
         interpret: bool | None = None,
         site: str = "", count: int = 1) -> jax.Array:
    """Triangular solve A X = B (A (m, m) triangular, B (m, n)).

    The Pallas path is a blocked substitution: row panels of ``bm``
    (from the tuned tile) retire in order — each one subtracts the
    already-solved prefix via the tuned matmul kernel, then solves its
    diagonal block against the jax.lax reference.  This mirrors the cost
    model's sequential-dependency term (one dependent launch per M
    panel).  Tuner lookups use routine="trsm" on the (m, m, n) shape,
    degrading to gemm on artifacts without trsm signal.
    """
    if a.ndim != 2 or a.shape[0] != a.shape[1] or b.ndim != 2 \
            or b.shape[0] != a.shape[0]:
        raise ValueError(f"bad TRSM shapes {a.shape} x {b.shape}")
    m = int(a.shape[0])
    n = int(b.shape[1])
    be = resolve_backend(backend)
    rt, cfg, hit = _select(m, m, n, "trsm",
                           tuner if tile is None else None,
                           need_config=be != "xla")
    recorder.record(rt, m, m, n, config=cfg, cache_hit=hit, site=site,
                    count=count)
    if be == "xla":
        return ref.trsm_ref(a, b, lower=lower, unit_diag=unit_diag)
    bm, bk, bn = (tile if tile is not None
                  else cfg.tile if cfg is not None else DEFAULT_TILES[3])
    interp = resolve_interpret(interpret)
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    starts = list(range(0, m, bm))
    if not lower:                 # backward substitution: bottom-up
        starts = starts[::-1]
    blocks: dict[int, jax.Array] = {}
    for i0 in starts:
        i1 = min(i0 + bm, m)
        rhs = b32[i0:i1]
        # subtract the already-solved panels' contribution in one tuned
        # matmul over the concatenated prefix (suffix for upper)
        done = [j0 for j0 in blocks if (j0 < i0 if lower else j0 > i0)]
        if done:
            done.sort()
            cols = jnp.concatenate(
                [a32[i0:i1, j0:min(j0 + bm, m)] for j0 in done], axis=1)
            solved = jnp.concatenate([blocks[j0] for j0 in done], axis=0)
            rhs = rhs - matmul_pallas(cols, solved, bm=bm, bk=bk, bn=bn,
                                      interpret=interp)
        blocks[i0] = jax.lax.linalg.triangular_solve(
            a32[i0:i1, i0:i1], rhs, left_side=True, lower=lower,
            unit_diagonal=unit_diag)
    x = jnp.concatenate([blocks[i0] for i0 in sorted(blocks)], axis=0)
    return x.astype(b.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, *,
                   tuner: AdsalaTuner | None = None,
                   tile: tuple[int, int, int] | None = None,
                   group_sizes: list[int] | None = None,
                   routine: str = DEFAULT_ROUTINE,
                   site: str = "grouped",
                   backend: Backend = "auto",
                   interpret: bool | None = None) -> jax.Array:
    """Y[e] = X[e] @ W[e] with tuner-selected tiling.

    ``group_sizes`` (actual tokens routed per expert, <= capacity) refines
    the per-expert GEMM shapes the tuner sees; with or without it, all E
    experts resolve through a single batched ``select_many`` lookup.
    Each per-expert shape is reported to any active recorder as its own
    event (the MoE dispatch volume is per-expert, not per-kernel).
    """
    be = resolve_backend(backend)
    e, c, d = x.shape
    f = w.shape[2]
    if group_sizes is not None:
        group_sizes = [int(g) for g in group_sizes]
        if len(group_sizes) != e:
            raise ValueError(
                f"group_sizes has {len(group_sizes)} entries for {e} "
                "experts; a prefix is not allowed — pass one size per "
                "expert (0 for an idle expert)")
        if any(g < 0 or g > c for g in group_sizes):
            raise ValueError(
                f"group_sizes {group_sizes} outside [0, capacity={c}]")
    # an expert with zero routed tokens still runs its capacity bucket;
    # query the tuner with at least one row so the shape stays sensible
    shapes = ([(max(int(g), 1), int(d), int(f)) for g in group_sizes]
              if group_sizes is not None
              else [(int(c), int(d), int(f))] * int(e))
    consult = tuner if tile is None else None
    rt = supported_routine(routine, consult)
    cfgs = None
    want_events = recorder.active()
    if consult is not None and (be != "xla" or want_events):
        hits = [consult.peek(m_, k_, n_, rt) for m_, k_, n_ in shapes]
        cfgs = consult.select_many(shapes, routines=rt)
    else:
        hits = [False] * len(shapes)
    if want_events:
        for (m_, k_, n_), hit, cfg in zip(
                shapes, hits, cfgs or [None] * len(shapes)):
            recorder.record(rt, m_, k_, n_, config=cfg, cache_hit=hit,
                            site=site)
    if be == "xla":
        return ref.grouped_matmul_ref(x, w)
    if tile is not None:
        bm, bk, bn = tile
    elif cfgs is not None:
        # one kernel tile serves every expert; use the config chosen for
        # the cost-dominant per-expert GEMM (largest m*k*n, not just m —
        # hint shapes may be heterogeneous in every dim)
        big = max(range(len(shapes)),
                  key=lambda i: shapes[i][0] * shapes[i][1] * shapes[i][2])
        bm, bk, bn = cfgs[big].tile
    else:
        bm, bk, bn = DEFAULT_TILES[3]  # (256, 256, 256)
    return grouped_matmul_pallas(x, w, bm=bm, bk=bk, bn=bn,
                                 interpret=resolve_interpret(interpret))


#: untuned-XLA fallback: the longest causal self-attention whose scores
#: the SYRK materialisation path serves when no tuner is available to
#: price the choice.  This retires the models.layers.SYRK_SCORES_MAX_SEQ
#: hardcode — a tuner with attn + syrk signal replaces the threshold
#: with a predicted-time comparison per shape.
SYRK_FALLBACK_MAX_SEQ = 512

#: hard memory guard on the SYRK score path (tuned or not): the full
#: fp32 (Sq, Sq) score triangle must fit this budget per head — the
#: chunked / flash paths keep only O(block x Skv) scores live, so past
#: this point materialisation is inadmissible at any predicted speed.
SYRK_SCORES_BYTES_MAX = 64 * 1024 * 1024


def _syrk_scores_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           sm_scale: float | None, *,
                           tuner: AdsalaTuner | None,
                           site: str, count: int) -> jax.Array:
    """Causal self-attention with materialised SYRK-shaped scores.

    With causal masking only the lower triangle of QK^T is ever
    consumed — exactly SYRK's output shape — so the score product
    dispatches (and is recorded, per head with its batch multiplicity)
    as routine="syrk" on the (Sq, Dh, Sq) triple.  q/k/v: (BH, Sq, Dh);
    computed in fp32 like the chunked path.
    """
    bh, sq, d = q.shape
    scale = sm_scale if sm_scale is not None else float(d) ** -0.5
    scores = jax.vmap(
        lambda qi, ki: syrk(qi, ki, tuner=tuner, site=site, count=count,
                            backend="xla"))(
        q.astype(jnp.float32), k.astype(jnp.float32))
    ids = jnp.arange(sq)
    mask = ids[None, :] <= ids[:, None]
    scores = jnp.where(mask[None], scores * scale, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def _chunked_attention_flat(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool, window: int | None,
                            sm_scale: float | None,
                            chunk: int = 512) -> jax.Array:
    """Online XLA attention scanned over query chunks, (BH, S, D) in/out.

    Never materialises the full (Sq, Skv) score matrix: per scan step
    the live block is (BH, chunk, Skv) — the long-sequence XLA path.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    scale = sm_scale if sm_scale is not None else float(d) ** -0.5
    nc = -(-sq // chunk)
    pad = nc * chunk - sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qc = qp.reshape(bh, nc, chunk, d).transpose(1, 0, 2, 3)
    kv_ids = jnp.arange(skv)

    def step(_, qi_ci):
        qi, ci = qi_ci
        s = jnp.einsum("bqd,bkd->bqk", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        q_ids = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, skv), dtype=bool)
        if causal:
            mask &= kv_ids[None, :] <= q_ids[:, None]
        if window is not None:
            mask &= kv_ids[None, :] > q_ids[:, None] - window
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(step, None, (qc, jnp.arange(nc)))
    return outs.transpose(1, 0, 2, 3).reshape(bh, nc * chunk, d)[:, :sq]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    sm_scale: float | None = None,
                    bq: int | None = None, bkv: int | None = None,
                    grid: str | None = None,
                    tuner: AdsalaTuner | None = None,
                    backend: Backend = "auto",
                    interpret: bool | None = None,
                    site: str = "attn.core",
                    count: int | None = None) -> jax.Array:
    """Tuned attention: softmax(q kᵀ, causal/windowed) v on (BH, S, D).

    Masked (causal or windowed) attention dispatches as routine="attn"
    on the per-head (Sq, Dh, Skv) triple with ``count`` (default BH)
    multiplicity; non-causal unwindowed attention keeps the gemm
    identity.  The tuner's chosen :class:`GemmConfig` supplies the
    flash blocks (``flash_block``) and the KV-grid kind
    (``flash_grid``: dense vs block-sparse triangular), and on the XLA
    backend whether the SYRK score-materialisation path wins instead —
    a predicted-time comparison per shape, replacing the retired
    ``SYRK_SCORES_MAX_SEQ`` hardcode (untuned XLA callers fall back to
    that threshold, :data:`SYRK_FALLBACK_MAX_SEQ`, under the
    :data:`SYRK_SCORES_BYTES_MAX` memory guard).  Explicit
    ``bq``/``bkv``/``grid`` overrides skip the tuner entirely, like
    ``matmul``'s explicit ``tile``.  Every path reports its dispatch —
    the SYRK path through :func:`syrk` itself (no double event), the
    flash/chunked paths as one attn/gemm event carrying the resolved
    config — to any active DispatchRecorder.
    """
    be = resolve_backend(backend)
    if q.ndim != 3 or k.shape != v.shape or q.shape[0] != k.shape[0] \
            or q.shape[2] != k.shape[2]:
        raise ValueError(f"bad attention shapes {q.shape} {k.shape}")
    bh, sq, d = (int(s) for s in q.shape)
    skv = int(k.shape[1])
    count = bh if count is None else count
    masked = causal or window is not None
    explicit = bq is not None or bkv is not None or grid is not None
    rt = supported_routine("attn" if masked else DEFAULT_ROUTINE,
                           None if explicit else tuner)
    cfg, hit = None, False
    if tuner is not None and not explicit:
        hit = tuner.peek(sq, d, skv, rt)
        cfg = tuner.select(sq, d, skv, rt)
    if cfg is not None and rt == "attn":
        fbq, fbkv = cfg.flash_block
        fgrid = cfg.flash_grid
    else:
        # untuned defaults: under a causal/window mask the block-sparse
        # grid is a pure win (it only drops all-masked tiles); without
        # a mask the two grids are the same tile list anyway
        fbq, fbkv, fgrid = 512, 512, ("tri" if masked else "dense")
    bq = bq if bq is not None else fbq
    bkv = bkv if bkv is not None else fbkv
    grid = grid if grid is not None else fgrid

    if be == "xla":
        if causal and window is None and sq == skv \
                and sq * sq * 4 <= SYRK_SCORES_BYTES_MAX:
            if cfg is not None and rt == "attn" \
                    and "syrk" in tuner.routines:
                _, t_attn = tuner.select_with_times(sq, d, skv, "attn")
                _, t_syrk = tuner.select_with_times(sq, d, sq, "syrk")
                use_syrk = float(np.min(t_syrk)) < float(np.min(t_attn))
            else:
                use_syrk = (tuner is None or rt != "attn") \
                    and sq <= SYRK_FALLBACK_MAX_SEQ
            if use_syrk:
                return _syrk_scores_attention(q, k, v, sm_scale,
                                              tuner=tuner, site=site,
                                              count=count)
        recorder.record(rt, sq, d, skv, config=cfg, cache_hit=hit,
                        site=site, count=count)
        return _chunked_attention_flat(q, k, v, causal=causal,
                                       window=window, sm_scale=sm_scale,
                                       chunk=min(512, max(1, sq)))
    recorder.record(rt, sq, d, skv, config=cfg, cache_hit=hit,
                    site=site, count=count)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  sm_scale=sm_scale, bq=bq, bkv=bkv,
                                  interpret=resolve_interpret(interpret),
                                  grid=grid)
