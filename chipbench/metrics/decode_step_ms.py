"""Device time of one decode program execution, averaged over the
window's executions (the trace's program runs)."""


def read(run):
    runs = [m for kind, m in run.trace.programs() if kind == "decode"]
    if not runs:
        return None
    return sum(m.dur for m in runs) / len(runs) / 1e6
