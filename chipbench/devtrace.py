"""Reduce a profiler trace to the numbers the per-layer metrics read.

A traced run writes JAX's profiler trace (``*.xplane.pb``); this module
reads it with ``jax.profiler.ProfileData`` and keeps three lists of
events on one clock: the device's operations (line ``XLA Ops`` of each
``/device:TPU:<n>`` plane), its program executions (line ``XLA
Modules``), and the host's events on the thread that drove the run
(the harness's own spans are named ``chipbench.*``).

Programs are told apart by what they hold, since the program names both
jitted steps ``<lambda>``: an execution whose operations include the
flash kernel is a prefill; the other executions of ``<lambda>`` are
decode steps; the rest (the eager page seeding, argmax, transfers) are
``other``. A trace in which no flash kernel is found falls back on
counts: the ``<lambda>`` program run most often is the decode step.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

__all__ = ["Event", "Trace", "load", "describe", "union_ns", "is_flash"]

#: an operation is the flash kernel when its name or its HLO names hold
#: one of the Pallas kernel's function names, or when it is a custom call
#: made inside the jitted ``flash_attention_pallas``
FLASH_MARKS = ("_flash_tri_kernel", "_flash_dense_kernel")
FLASH_JIT = "flash_attention_pallas"

#: the harness's span names start with this
SPAN = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    start: int           # ns, on the trace's clock
    dur: int             # ns
    name: str
    stats: tuple = ()    # ((key, value), ...) as the trace gives them

    @property
    def end(self) -> int:
        return self.start + self.dur

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default

    def text(self) -> str:
        """Name and string stats, for matching kernel names."""
        return " ".join([self.name] + [str(v) for _, v in self.stats
                                       if isinstance(v, str)])


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals within
    ``[lo, hi)``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]        # device index -> operations
    modules: dict[int, list[Event]]    # device index -> program runs
    host: list[Event]                  # the driving thread's events

    # -- the window ------------------------------------------------------
    def window(self) -> tuple[int, int]:
        """The measured window: the harness's ``chipbench.window``
        span."""
        for e in self.host:
            if e.name == SPAN + "window":
                return e.start, e.end
        raise ValueError("the trace holds no chipbench.window span")

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        lo, hi = self.window()
        per = [union_ns([(e.start, e.end) for e in ops], lo, hi)
               for ops in self.ops.values() if ops]
        return sum(per) / len(per) / 1e9 if per else 0.0

    # -- programs --------------------------------------------------------
    def _dev(self) -> int | None:
        return min((d for d, ops in self.ops.items() if ops), default=None)

    def programs(self) -> list[tuple[str, Event]]:
        """``(kind, execution)`` of every program run on the first
        device that overlaps the window; kind is prefill, decode or
        other."""
        lo, hi = self.window()
        dev = self._dev()
        if dev is None:
            return []
        ops = self.ops[dev]
        starts = [e.start for e in ops]
        runs = [m for m in self.modules.get(dev, [])
                if m.end > lo and m.start < hi]
        holds = []
        for m in runs:
            i = bisect.bisect_left(starts, m.start)
            j = bisect.bisect_right(starts, m.end)
            holds.append(any(is_flash(e) for e in ops[i:j]))
        # without a flash kernel to go by, the decode program is the
        # <lambda> program run most often (once a step; a prefill program
        # once per admission of its length)
        decode_id = None
        if not any(holds):
            counts: dict = defaultdict(int)
            for m in runs:
                if "lambda" in m.name:
                    counts[m.stat("program_id", m.name)] += 1
            decode_id = max(counts, key=counts.get) if counts else None
        out = []
        for m, flash in zip(runs, holds):
            if flash:
                kind = "prefill"
            elif "lambda" not in m.name:
                kind = "other"
            elif decode_id is None or m.stat("program_id", m.name) \
                    == decode_id:
                kind = "decode"
            else:
                kind = "prefill"
            out.append((kind, m))
        return out

    def flash_ops(self) -> list[Event]:
        lo, hi = self.window()
        return [e for e in self.ops.get(self._dev(), [])
                if is_flash(e) and lo <= e.start < hi]

    # -- breakdown -------------------------------------------------------
    def top_ops(self, n: int = 10) -> list[list]:
        """The device operations that took most time in the window, by
        self time (an operation's time less that of the operations it
        encloses), named ``<program kind>:<op>``."""
        lo, hi = self.window()
        dev = self._dev()
        if dev is None:
            return []
        kinds = self.programs()
        kind_at = sorted((m.start, m.end, k) for k, m in kinds)
        kstarts = [s for s, _, _ in kind_at]
        total: dict[str, float] = defaultdict(float)
        ops = sorted(self.ops[dev], key=lambda e: (e.start, -e.dur))
        stack: list[list] = []      # [event, child ns]

        def close(item):
            e, child = item
            if lo <= e.start < hi:
                i = bisect.bisect_right(kstarts, e.start) - 1
                kind = (kind_at[i][2] if i >= 0 and e.start < kind_at[i][1]
                        else "other")
                total[f"{kind}:{_short(e.name)}"] += (e.dur - child) / 1e9

        for e in ops:
            while stack and stack[-1][0].end <= e.start:
                close(stack.pop())
            if stack:
                stack[-1][1] += e.dur
            stack.append([e, 0])
        while stack:
            close(stack.pop())
        best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in best]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest stretches of the window with no device operation,
        each named by what the driving thread was doing in its middle:
        the innermost harness span, then the innermost other host event
        there."""
        lo, hi = self.window()
        dev = self._dev()
        if dev is None:
            return []
        gaps = _gaps([(e.start, e.end) for e in self.ops[dev]], lo, hi)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            here = [h for h in self.host
                    if h.start <= mid < h.end and h.name != SPAN + "window"]
            span = min((h for h in here if h.name.startswith(SPAN)),
                       key=lambda h: h.dur, default=None)
            other = min((h for h in here if not h.name.startswith(SPAN)),
                        key=lambda h: h.dur, default=None)
            label = " > ".join(x.name for x in (span, other) if x) \
                or "no host span"
            out.append([label, (e - s) / 1e9])
        return out


def _short(name: str) -> str:
    return name if len(name) <= 80 else name[:77] + "..."


def is_flash(e: Event) -> bool:
    text = e.text()
    if any(m in text for m in FLASH_MARKS):
        return True
    return FLASH_JIT in text and ("custom-call" in e.name
                                  or "custom_call" in text)


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        stats = tuple((k, v) for k, v in e.stats)
        out.append(Event(int(e.start_ns), int(e.duration_ns), e.name, stats))
    return out


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read a trace file (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find(path)
    data = ProfileData.from_file(path)
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = sorted(_events(line), key=lambda e: e.start)
                elif line.name == "XLA Modules":
                    modules[dev] = sorted(_events(line),
                                          key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(e.name.startswith(SPAN) for e in evs):
                    host.extend(evs)
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def describe(path: str, n: int = 40) -> dict:
    """The trace's planes, lines, busiest event names and sample stats:
    for reading a trace by hand."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = _events(line)
            tot: dict[str, list] = defaultdict(lambda: [0, 0])
            for e in evs:
                tot[e.name][0] += 1
                tot[e.name][1] += e.dur
            top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:n]
            lines.append({
                "line": line.name, "events": len(evs),
                "first_ns": evs[0].start if evs else None,
                "last_ns": evs[-1].end if evs else None,
                "top": [[k, c, d / 1e9] for k, (c, d) in top],
                "samples": [[e.name, e.start, e.dur,
                             [[k, str(v)[:200]] for k, v in e.stats]]
                            for e in evs[:5]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"file": path, "planes": out}
