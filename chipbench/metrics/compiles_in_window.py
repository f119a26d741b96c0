"""Programs built (compiled, or loaded from the persistent cache) while
the window was open, from JAX's monitoring events: 0 when set-up warmed
every program."""


def read(run):
    return run.counters["compiles_in_window"]
