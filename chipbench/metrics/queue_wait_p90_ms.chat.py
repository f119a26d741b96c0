"""p90 of the time requests waited in the scheduler's queue, from
submission to admission (the ``queue_wait_ms`` stat of each
``serve.admit`` span that starts in the window)."""

import numpy as np


def read(run):
    lo, hi = run.trace.window()
    waits = [e.stat("queue_wait_ms") for e in run.trace.host
             if e.name == "serve.admit" and lo <= e.start < hi]
    return float(np.percentile(waits, 90)) if waits else None
