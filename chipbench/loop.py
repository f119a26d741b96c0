"""Drive the continuous-batching scheduler and log what it serves.

The harness submits requests and calls ``sched.step()``; after each step
it reads which slot holds which request and how many tokens each has, so
every output token gets the host time of the end of the step that
produced it (the scheduler keeps no timestamps of its own). Each step is
a host span ``chipbench.step.admit`` (requests were waiting for a slot)
or ``chipbench.step.decode``; the measured window is the span
``chipbench.window``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from traffic import Req

__all__ = ["ReqLog", "StepLog", "Log", "Server"]

clock = time.perf_counter


@dataclasses.dataclass
class ReqLog:
    req: Req
    due: float | None          # host time it was due (open loop)
    submitted: float
    times: list = dataclasses.field(default_factory=list)  # per token
    tokens: tuple | None = None                             # when done

    @property
    def done(self) -> bool:
        return self.tokens is not None


@dataclasses.dataclass
class StepLog:
    start: float
    end: float
    prefills: list      # prompt lengths admitted in this step
    decode_ctx: list    # keys each decoded token attended over
    pending: int = 0    # requests still waiting for a slot after it


@dataclasses.dataclass
class Log:
    reqs: dict = dataclasses.field(default_factory=dict)   # rid -> ReqLog
    steps: list = dataclasses.field(default_factory=list)
    w0: float = 0.0                 # the window, host clock
    w1: float = 0.0
    traced_steps: tuple = (0, 0)    # steps inside the chipbench.window span
    lateness: list = dataclasses.field(default_factory=list)

    def window_reqs(self) -> list[int]:
        """Requests that fell due in the window (open loop), or that
        finished in it (offline)."""
        out = []
        for rid, r in self.reqs.items():
            if r.due is not None:
                if self.w0 <= r.due < self.w1:
                    out.append(rid)
            elif r.done and r.times and self.w0 <= r.times[-1] < self.w1:
                out.append(rid)
        return out

    def tokens_in_window(self) -> int:
        return sum(int(np.sum((t >= self.w0) & (t < self.w1)))
                   for t in (np.asarray(r.times) for r in self.reqs.values()))

    def ttft_s(self) -> list[float]:
        return [self.reqs[rid].times[0] - self.reqs[rid].due
                for rid in self.window_reqs() if self.reqs[rid].times]

    def itl_s(self) -> list[float]:
        out = []
        for r in self.reqs.values():
            t = np.asarray(r.times)
            if len(t) > 1:
                later = t[1:]
                sel = (later >= self.w0) & (later < self.w1)
                out.extend(np.diff(t)[sel].tolist())
        return out


class Server:
    """One scheduler under one traffic stream, with its log."""

    def __init__(self, sched, hooks=None) -> None:
        self.sched = sched
        self.log = Log()
        self._rid = 0
        self._n_done = 0
        self._window_span = None
        #: called with "start" / "end" as the window opens and closes
        self.hooks = hooks

    # -- intake and bookkeeping -----------------------------------------
    def submit(self, req: Req, due: float | None = None) -> int:
        rid = self._rid
        self._rid += 1
        now = clock()
        self.sched.submit(req.tokens.tolist(), req.out_len, rid=rid)
        self.log.reqs[rid] = ReqLog(req, due, now)
        if due is not None:
            self.log.lateness.append(now - due)
        return rid

    def step(self) -> bool:
        s = self.sched
        name = ("chipbench.step.admit" if s.pending and s.active < s.slots
                else "chipbench.step.decode")
        t0 = clock()
        with TraceAnnotation(name):
            did = s.step()
        t1 = clock()
        self._record(t0, t1)
        return did

    def _record(self, t0: float, t1: float) -> None:
        s = self.sched
        counts: dict[int, int] = {}
        for i, req in enumerate(s._req):
            if req is not None:
                counts[req.rid] = len(s._gen[i])
        fin = list(s.finished.items())[self._n_done:]
        self._n_done = len(s.finished)
        for rid, f in fin:
            counts[rid] = len(f.tokens)
        step = StepLog(t0, t1, [], [], s.pending)
        for rid, n in counts.items():
            r = self.log.reqs.get(rid)
            if r is None:           # warm-up requests are not logged
                continue
            have = len(r.times)
            if n <= have:
                continue
            first = have == 0
            if first:
                step.prefills.append(r.req.prompt_len)
            for j in range(have + (1 if first else 0), n):
                # token j is decoded from position prompt + j - 1 and
                # attends over prompt + j keys
                step.decode_ctx.append(r.req.prompt_len + j)
            r.times.extend([t1] * (n - have))
        for rid, f in fin:
            if rid in self.log.reqs:
                self.log.reqs[rid].tokens = f.tokens
        self.log.steps.append(step)

    # -- the window span ------------------------------------------------
    def _open(self) -> None:
        self._window_span = TraceAnnotation("chipbench.window")
        self._window_span.__enter__()
        self._i0 = len(self.log.steps)
        if self.hooks:
            self.hooks("start")

    def _close(self) -> None:
        self._window_span.__exit__(None, None, None)
        self._window_span = None
        self.log.traced_steps = (self._i0, len(self.log.steps))
        if self.hooks:
            self.hooks("end")

    # -- loops ----------------------------------------------------------
    def warm_up(self, reqs: list[Req]) -> None:
        """Serve ``reqs`` to the end, unlogged: compiles every program
        they touch."""
        for r in reqs:
            self.sched.submit(r.tokens.tolist(), r.out_len, rid=self._rid)
            self._rid += 1
        self.sched.run_until_drained()
        self._n_done = len(self.sched.finished)

    def run_open(self, stream, seconds: float, drain_s: float) -> Log:
        """Offer ``stream`` on its schedule; the window opens at arrival
        time 0. Returns once every request due in the window has
        finished, or ``drain_s`` after the window closes."""
        log, s = self.log, self.sched
        nxt = next(stream)
        origin = clock() - nxt.arrival          # first arrival due now
        log.w0, log.w1 = origin, origin + seconds
        due_in_window: list[int] = []
        state = "lead"
        while True:
            now = clock()
            while origin + nxt.arrival <= now:
                rid = self.submit(nxt, origin + nxt.arrival)
                if 0 <= nxt.arrival < seconds:
                    due_in_window.append(rid)
                nxt = next(stream)
            if state == "lead" and now >= log.w0:
                self._open()
                state = "window"
            if state == "window" and now >= log.w1:
                self._close()
                state = "drain"
            if state == "drain" and (
                    all(log.reqs[r].done for r in due_in_window)
                    or now > log.w1 + drain_s):
                return log
            if s.active == 0 and s.pending == 0:
                wait = origin + nxt.arrival - clock()
                if wait > 0:
                    time.sleep(min(wait, 0.001))
                continue
            self.step()

    def _full(self) -> bool:
        """Every slot busy, or the head of the queue waits for pages."""
        s = self.sched
        if s.active == s.slots:
            return True
        head = s._queue[0] if s._queue else None
        return head is not None and not s.alloc.can_admit(
            len(head.prompt) + head.max_new - 1)

    def run_offline(self, stream, seconds: float, depth: int) -> Log:
        """Keep ``depth`` requests waiting; the window opens once the
        server is full and lasts ``seconds``."""
        log, s = self.log, self.sched
        for _ in range(s.slots + depth):
            self.submit(next(stream))
        self.step()
        while not self._full():
            while s.pending < depth:
                self.submit(next(stream))
            self.step()
        log.w0 = clock()
        log.w1 = log.w0 + seconds
        self._open()
        while clock() < log.w1:
            while s.pending < depth:
                self.submit(next(stream))
            self.step()
        self._close()
        return log
