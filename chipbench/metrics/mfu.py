"""The whole step's share of the chip's peak: the operations the served
tokens need (prefills under a causal mask and each decoded token at its
live context, the family's count) over the traced window times the
bf16 peak."""


def read(run):
    w = run.trace.window_s()
    f = run.window_flops()
    if w <= 0 or f <= 0:
        return None
    return 100.0 * f / (w * run.peak["bf16_flops"])
