"""The flash kernel's share of its roofline: the least time its causal
work could take on the chip (the larger of operations over peak and
bytes over bandwidth, flops.py) over the kernel's device time. Read only
where the trace holds one kernel call per layer of every admitted
prompt."""

import flops


def read(run):
    calls = run.trace.flash_ops()
    lengths = run.prefill_lengths()
    k = run.k
    if not calls or len(calls) != k["layers"] * len(lengths):
        return None
    itemsize = 2    # bfloat16 activations
    least = k["layers"] * sum(
        flops.roofline_s(flops.flash_flops(k["heads"], n, k["head_dim"]),
                         flops.flash_bytes(k["heads"], n, k["head_dim"],
                                           itemsize), run.peak)
        for n in lengths)
    return 100.0 * least / (sum(e.dur for e in calls) / 1e9)
