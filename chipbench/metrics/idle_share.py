"""Share of the window in which no operation ran on the device."""


def read(run):
    w = run.trace.window_s()
    return 100.0 * (1.0 - run.trace.busy_s() / w) if w > 0 else None
