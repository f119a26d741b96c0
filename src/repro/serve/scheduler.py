"""Continuous-batching decode loop over the paged KV cache.

Fixed-batch serving admits one batch and steps it until the *slowest*
sequence finishes: every early-finishing slot idles, so goodput on
ragged-length traffic collapses toward the longest request.  The
scheduler here keeps a fixed number of decode **slots** and a shared
page pool (:mod:`repro.serve.kv_cache`); per step it

1. admits queued requests into free slots — the prompt is prefilled at
   its exact length and its cache rows are seeded into freshly
   allocated pages,
2. decodes one token for *every* active slot with a single jitted
   paged ``decode_step`` (fixed shapes: the jit never retraces as
   sequences come and go),
3. retires finished sequences immediately — their pages re-enter the
   free list and the freed slot can admit the next request on the same
   step.

Admission reserves the worst case up front
(``pages_for(prompt + max_new - 1)``), so a running sequence can never
deadlock mid-decode waiting for pages; requests are admitted strictly
FIFO (a request that does not fit blocks the queue head — no
starvation of long prompts by short ones).

Dispatch observability: prefill traces record into the ``"prefill"``
recorder, decode traces into ``"decode"`` — the same per-traffic-class
split :mod:`repro.launch.serve` feeds the
:class:`~repro.serve.reinstall.ReinstallManager`, so the live ragged
mix drives online re-installs unchanged.  Recording is trace-time: a
new prompt length is a new prefill trace, so the recorded mix tracks
the shape diversity actually admitted.

Timing: every request carries host timestamps (``time.perf_counter``)
of its submission, its admission and the moment each generated token's
value reached the host (:class:`FinishedSeq`). Under JAX's profiler the
scheduler's phases are host spans on the trace's clock, each with
numeric stats; spans of one request share its ``rid``:

- ``serve.step`` (``step``, ``active``, ``pending``): one ``step()``;
- ``serve.admit`` (``rid``, ``prompt_len``, ``queue_wait_ms``): one
  admission, from taking the request off the queue to its first token
  on the host, holding ``serve.prefill`` (``rid``, ``prompt_len``) and
  ``serve.seed_pages`` (``rid``, ``pages``);
- ``serve.decode`` (``active``): the decode dispatch through the fetch
  of the next tokens.

With the profiler off a span costs about a microsecond.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.kv_cache import (
    HOLE,
    PageAllocator,
    pages_for,
    seed_pages,
)

__all__ = ["Request", "FinishedSeq", "ContinuousBatchingScheduler"]


@dataclasses.dataclass(frozen=True)
class Request:
    """One queued generation request (ragged prompt/output lengths)."""

    rid: int
    prompt: tuple[int, ...]
    max_new: int
    submitted_at: float        # host clock (``time.perf_counter``)


@dataclasses.dataclass(frozen=True)
class FinishedSeq:
    """A retired sequence: the generated ids plus scheduling metadata.

    Times are host-clock seconds (``time.perf_counter``): queue wait is
    ``admitted_at - submitted_at``, time to first token
    ``token_times[0] - submitted_at``; ``token_times[i]`` is when
    ``tokens[i]`` reached the host."""

    rid: int
    prompt: tuple[int, ...]
    tokens: tuple[int, ...]
    admitted_step: int
    finished_step: int
    submitted_at: float
    admitted_at: float
    token_times: tuple[float, ...]


def _seed_segment(pool_seg: Any, cache_seg: Any, page_ids: jax.Array,
                  stacked: bool) -> Any:
    """Write a prefill cache segment into the matching pool segment.

    The two trees differ in node type (``KVCache`` vs ``PagedKV``,
    ``MLACache`` vs ``PagedLatent``) but align leaf-for-leaf — k with
    k, v with v, c_kv with c_kv — so the zip below is the whole
    mapping.  ``stacked`` handles the scan segment's extra leading
    repeat dim ((R, 1, cap, ...) rows into (R, P, page, ...) pools).
    """
    leaves, treedef = jax.tree.flatten(pool_seg)
    vals = jax.tree.leaves(cache_seg)
    if len(leaves) != len(vals):
        raise ValueError(
            f"pool/prefill cache leaf mismatch ({len(leaves)} vs "
            f"{len(vals)}) — unsupported cache variant for paging")
    out = []
    for pl, vl in zip(leaves, vals):
        if stacked:
            out.append(jax.vmap(
                lambda pool, rows: seed_pages(pool, page_ids, rows)
            )(pl, vl[:, 0]))
        else:
            out.append(seed_pages(pl, page_ids, vl[0]))
    return jax.tree.unflatten(treedef, out)


class ContinuousBatchingScheduler:
    """Admit/retire-per-step decode loop over a shared page pool.

    Parameters
    ----------
    model, cfg, params : the LM triple (``repro.configs.build_model``).
    slots : decode batch width — the fixed shape of the jitted step.
    n_pages, page_size : the shared pool (total token slots in flight
        = ``n_pages * page_size``, the real memory ceiling).
    max_seq_len : per-sequence cap (prompt + generated); sets the page
        table width, and with it the gathered attention span.
    tuner : optional ADSALA tuner / ReinstallManager facade, threaded
        into every routine-aware call site of prefill and decode.
    recorders : ``{"prefill": DispatchRecorder, "decode": ...}`` — the
        per-traffic-class recorders; created when omitted.
    eos_id : optional early-stop token id (None = run to ``max_new``).

    Thread safety: ``submit`` may be called from any thread while one
    consumer thread runs ``step``/``run_until_drained``.
    """

    def __init__(self, model, cfg, params, *, slots: int, n_pages: int,
                 page_size: int, max_seq_len: int, tuner=None,
                 recorders: dict | None = None, dtype=jnp.float32,
                 eos_id: int | None = None) -> None:
        if not hasattr(model, "init_paged_cache"):
            raise NotImplementedError(
                "continuous batching needs a decoder-only LM with a "
                "paged cache (encoder-decoder serving is fixed-batch)")
        if slots < 1:
            raise ValueError(f"slots={slots} < 1")
        from repro.kernels.recorder import DispatchRecorder
        from repro.train.step import make_ctx

        self.model, self.cfg, self.params = model, cfg, params
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.table_pages = pages_for(max_seq_len, page_size)
        #: gathered attention span per sequence (token slots)
        self.cap = self.table_pages * self.page_size
        self.tuner = tuner
        self.eos_id = eos_id
        self.recorders = recorders if recorders is not None else {
            "prefill": DispatchRecorder(), "decode": DispatchRecorder()}
        self.alloc = PageAllocator(n_pages, page_size)

        self._dtype = dtype
        self._make_ctx = make_ctx
        self._dctx = make_ctx(None, "decode", cache_len=self.cap,
                              tuner=tuner)
        self.pool = model.init_paged_cache(n_pages, page_size,
                                           self._dctx, dtype)

        # host-side slot state
        self._table = np.full((slots, self.table_pages), HOLE, np.int32)
        self._pos = np.full((slots,), -1, np.int32)
        self._tok = np.zeros((slots,), np.int32)
        self._req: list[Request | None] = [None] * slots
        self._gen: list[list[int]] = [[] for _ in range(slots)]
        self._times: list[list[float]] = [[] for _ in range(slots)]
        self._admit_step = [0] * slots
        self._admitted_at = [0.0] * slots

        self._lock = threading.Lock()
        self._queue: deque[Request] = deque()
        self._rids: set[int] = set()
        self._next_rid = 0
        self.finished: dict[int, FinishedSeq] = {}
        self.steps = 0
        self.admitted = 0

        self._decode = jax.jit(
            lambda p, pool, tok, pos, table: model.decode_step(
                p, tok, pool, pos, self._dctx, table),
            donate_argnums=(1,))
        self._prefills: dict[int, Callable] = {}

    # -- request intake -------------------------------------------------
    def submit(self, prompt, max_new: int, rid: int | None = None) -> int:
        """Queue one request; returns its rid.  Raises when the request
        could *never* run (exceeds the per-sequence cap or the whole
        pool) — deferral is for transient exhaustion only."""
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new={max_new} < 1")
        # the last generated token is returned, never written back, so
        # the sequence stores prompt + max_new - 1 token slots
        total = len(prompt) + max_new - 1
        if total > self.cap:
            raise ValueError(
                f"request needs {total} token slots > per-sequence cap "
                f"{self.cap} (max_seq_len)")
        if pages_for(total, self.page_size) > self.n_pages:
            raise ValueError(
                f"request needs {pages_for(total, self.page_size)} pages "
                f"> pool size {self.n_pages}: can never be admitted")
        with self._lock:
            if rid is None:
                while self._next_rid in self._rids:
                    self._next_rid += 1
                rid = self._next_rid
            if rid in self._rids:
                raise ValueError(f"duplicate rid {rid}")
            self._rids.add(rid)
            self._queue.append(
                Request(rid, prompt, max_new, time.perf_counter()))
        return rid

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._req)

    # -- admission ------------------------------------------------------
    def _prefill_fn(self, prompt_len: int) -> Callable:
        fn = self._prefills.get(prompt_len)
        if fn is None:
            # cache_len = whole pages, so the seeded rows reshape
            # cleanly into pages; prompt runs at its exact length so
            # logits_last sees the real last token, not padding
            cache_len = pages_for(prompt_len, self.page_size) \
                * self.page_size
            pctx = self._make_ctx(None, "prefill", cache_len=cache_len,
                                  remat=False, tuner=self.tuner)
            fn = jax.jit(
                lambda p, toks: self.model.prefill(p, toks, pctx))
            self._prefills[prompt_len] = fn
        return fn

    def _admit(self) -> None:
        while True:
            slot = next((i for i in range(self.slots)
                         if self._req[i] is None), None)
            if slot is None:
                return
            with self._lock:
                if not self._queue:
                    return
                req = self._queue[0]
                pages = self.alloc.admit(
                    req.rid, len(req.prompt) + req.max_new - 1)
                if pages is None:        # transient exhaustion: defer
                    return
                self._queue.popleft()
            self._start(slot, req, pages)

    def _start(self, slot: int, req: Request, pages: list[int]) -> None:
        admitted_at = time.perf_counter()
        n_prompt = len(req.prompt)
        with TraceAnnotation(
                "serve.admit", rid=req.rid, prompt_len=n_prompt,
                queue_wait_ms=1e3 * (admitted_at - req.submitted_at)):
            n_prompt_pages = pages_for(n_prompt, self.page_size)
            toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None, :])
            with TraceAnnotation("serve.prefill", rid=req.rid,
                                 prompt_len=n_prompt), \
                    self.recorders["prefill"]:
                logits, cache = self._prefill_fn(n_prompt)(
                    self.params, toks)
            with TraceAnnotation("serve.seed_pages", rid=req.rid,
                                 pages=n_prompt_pages):
                page_ids = jnp.asarray(
                    np.asarray(pages[:n_prompt_pages], np.int32))
                pool = self.pool
                self.pool = {
                    "prefix": _seed_segment(pool["prefix"], cache["prefix"],
                                            page_ids, stacked=False),
                    "scan": (_seed_segment(pool["scan"], cache["scan"],
                                           page_ids, stacked=True)
                             if self.model.repeats else pool["scan"]),
                    "suffix": _seed_segment(pool["suffix"], cache["suffix"],
                                            page_ids, stacked=False),
                }
            first = int(jnp.argmax(logits[0]))
        self._times[slot] = [time.perf_counter()]
        row = np.full((self.table_pages,), HOLE, np.int32)
        row[: len(pages)] = pages
        self._table[slot] = row
        self._pos[slot] = n_prompt          # next decode writes here
        self._tok[slot] = first
        self._req[slot] = req
        self._gen[slot] = [first]
        self._admit_step[slot] = self.steps
        self._admitted_at[slot] = admitted_at
        self.admitted += 1
        if req.max_new == 1 or first == self.eos_id:
            self._retire(slot)              # finished at prefill

    # -- the decode step ------------------------------------------------
    def step(self) -> bool:
        """Admit, decode one token for every active slot, retire.

        Returns False when there was nothing to do (no active slots
        after admission)."""
        with TraceAnnotation("serve.step", step=self.steps,
                             active=self.active, pending=self.pending):
            return self._step()

    def _step(self) -> bool:
        self._admit()
        active = self.active
        if active == 0:
            return False
        with TraceAnnotation("serve.decode", active=active):
            with self.recorders["decode"]:
                logits, self.pool = self._decode(
                    self.params, self.pool,
                    jnp.asarray(self._tok[:, None]),
                    jnp.asarray(self._pos), jnp.asarray(self._table))
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        now = time.perf_counter()
        self.steps += 1
        for i in range(self.slots):
            req = self._req[i]
            if req is None:
                continue
            tok = int(nxt[i])
            self._gen[i].append(tok)
            self._times[i].append(now)
            self._pos[i] += 1
            self._tok[i] = tok
            if len(self._gen[i]) >= req.max_new or tok == self.eos_id:
                self._retire(i)
        self._admit()    # freed pages/slots serve the queue same-step
        return True

    def _retire(self, slot: int) -> None:
        req = self._req[slot]
        with self._lock:
            freed = self.alloc.retire(req.rid)
            assert freed == pages_for(
                len(req.prompt) + req.max_new - 1, self.page_size)
            self.finished[req.rid] = FinishedSeq(
                req.rid, req.prompt, tuple(self._gen[slot]),
                self._admit_step[slot], self.steps, req.submitted_at,
                self._admitted_at[slot], tuple(self._times[slot]))
        self._table[slot] = HOLE
        self._pos[slot] = -1
        self._tok[slot] = 0
        self._req[slot] = None
        self._gen[slot] = []
        self._times[slot] = []

    def run_until_drained(self, on_step: Callable | None = None,
                          max_steps: int = 1_000_000
                          ) -> dict[int, FinishedSeq]:
        """Step until queue and slots are empty; returns finished map.

        ``on_step(self)`` fires after every decode step — the hook the
        serve launcher uses for ReinstallManager drift checks.
        """
        idle_checks = 0
        while True:
            did = self.step()
            if did:
                idle_checks = 0
                if on_step is not None:
                    on_step(self)
            else:
                if self.pending == 0 and self.active == 0:
                    return dict(self.finished)
                idle_checks += 1
                if idle_checks > self.slots + 1:
                    raise RuntimeError(
                        "scheduler wedged: queued requests but nothing "
                        "admitted — pool/slot accounting broken")
            if self.steps > max_steps:
                raise RuntimeError(f"exceeded max_steps={max_steps}")

    # -- reporting ------------------------------------------------------
    @property
    def generated_tokens(self) -> int:
        return sum(len(f.tokens) for f in self.finished.values())

    def goodput(self) -> float:
        """Generated tokens per slot-step — 1.0 means every decode slot
        produced a kept token every step (the continuous-batching
        headline number; fixed-batch serving pays idle slots here)."""
        if self.steps == 0:
            return 0.0
        return self.generated_tokens / (self.steps * self.slots)
