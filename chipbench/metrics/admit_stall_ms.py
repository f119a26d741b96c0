"""Median duration of the scheduler's ``serve.admit`` spans that start
in the window: one admission, from taking a request off the queue to its
first token on the host (prefill, page seeding, the first token's
fetch), while every decode slot waits."""

import statistics


def read(run):
    lo, hi = run.trace.window()
    durs = [e.dur for e in run.trace.host
            if e.name == "serve.admit" and lo <= e.start < hi]
    return statistics.median(durs) / 1e6 if durs else None
