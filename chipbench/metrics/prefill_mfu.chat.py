"""Prefill's share of the chip's peak while it runs: the prompts'
operations (the family's count) over the device time of the prefill
programs times the bf16 peak. Read only where the trace holds as many
prefill runs as the harness admitted."""


def read(run):
    runs = [m for kind, m in run.trace.programs() if kind == "prefill"]
    lengths = run.prefill_lengths()
    if not runs or len(runs) != len(lengths):
        return None
    f = run.prefill_flops()
    t = sum(m.dur for m in runs) / 1e9
    return 100.0 * f / (t * run.peak["bf16_flops"])
