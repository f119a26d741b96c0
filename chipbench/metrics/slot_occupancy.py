"""Mean share of the decode slots that decoded a token, over the
window's steps (the scheduler's slot state after each step)."""


def read(run):
    steps = run.traced_steps()
    if not steps:
        return None
    return 100.0 * sum(len(s.decode_ctx) for s in steps) / (
        len(steps) * run.slots)
