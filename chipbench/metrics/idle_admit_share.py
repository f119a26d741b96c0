"""Share of the window in which the host is inside a ``serve.admit``
span and no operation runs on the device, averaged over the devices
that ran any: the idle time admission costs. Read only where the trace
holds the scheduler's ``serve.step`` spans."""

import bisect

import devtrace


def _merged(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read(run):
    tr = run.trace
    lo, hi = tr.window()
    if not any(e.name == "serve.step" and e.end > lo and e.start < hi
               for e in tr.host):
        return None
    admits = [(max(e.start, lo), min(e.end, hi)) for e in tr.host
              if e.name == "serve.admit" and e.end > lo and e.start < hi]
    per = []
    for ops in tr.ops.values():
        if not ops:
            continue
        busy = _merged((e.start, e.end) for e in ops)
        starts = [s for s, _ in busy]
        idle = 0
        for s, e in admits:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            j = bisect.bisect_left(starts, e)
            idle += (e - s) - devtrace.union_ns(busy[i:j], s, e)
        per.append(idle)
    if not per:
        return None
    return 100.0 * sum(per) / len(per) / (hi - lo)
