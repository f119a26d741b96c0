"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the requests the window served, drawn from the seed and always
holding the longest, is run through the plain reference: each prompt
with its served tokens, every position at once. The number compared is
the widest gap by which a served token's reference logit lies below the
reference's best at that position. The traffic is greedy, so a sound
server only ever trails the best by rounding.
"""

from __future__ import annotations

import numpy as np

from reference.common import served_gaps
from traffic import seed_rng

__all__ = ["sample", "compare"]


def sample(log, rids: list[int], seed: int, min_tokens: int,
           max_requests: int) -> list[int]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in rids if log.reqs[r].done]
    if not done:
        return []
    size = lambda r: log.reqs[r].req.prompt_len + len(log.reqs[r].tokens)
    longest = max(done, key=size)
    rest = [r for r in done if r != longest]
    pick, total = [longest], len(log.reqs[longest].tokens)
    for i in seed_rng(seed, 99).permutation(len(rest)):
        if total >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[i])
        total += len(log.reqs[rest[i]].tokens)
    return pick


def compare(cell, seed: int, log, rids: list[int], control: bool = False,
            length: int = 0) -> dict:
    """Widest served gap over the sampled requests against the cell's
    family's reference (and the control's, read at the same positions,
    with ``control``). Every sequence is padded to ``length``, so the
    reference compiles once per cell."""
    ref = cell.family.Reference(cell.k, seed)
    served, ctrl, n = 0.0, 0.0, 0
    for rid in rids:
        r = log.reqs[rid]
        g = served_gaps(ref, r.req.tokens, np.asarray(r.tokens, np.int32),
                        control=control, length=length)
        served = max(served, float(g["served"].max()))
        n += len(g["served"])
        if control:
            ctrl = max(ctrl, float(g["control"].max()))
    out = {"logit_gap": served, "tokens": n, "requests": len(rids)}
    if control:
        out["control_gap"] = ctrl
    return out
