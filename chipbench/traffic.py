"""The one traffic generator: every mix is a data file it reads.

A mix file (``traffic/<mix>.json``) gives the loop kind, the length
distributions, the slots and the page pool. Every mix is one fixed cycle
of requests, the same for every seed: ``n`` sizes (and, open loop, ``n``
gaps) taken at the ``n`` mid-quantiles of their distributions and put in
an order drawn from ``base_seed``. ``--seed`` picks where in the cycle a
run starts (a rotation of the one order) and draws the prompt tokens, so
every seed offers the same work in another order. Two loops:

* ``open``: arrivals on a schedule at ``rate_per_s``, whatever the server
  is doing. The cycle is one window long and holds exactly
  ``n = round(rate * seconds)`` arrivals, their gaps scaled to fill it;
  the schedule repeats it without end. ``lead_s`` of the cycle before the
  window is offered first, so the window opens on a loaded server.
* ``offline``: a queue kept at ``queue_depth`` pending requests, repeating
  a cycle of ``base_requests`` sizes. The first ``slots`` requests have
  their outputs cut to an even spread of remainders (1/slots, 2/slots,
  ... of their length), the state a long-running queue is in, so the
  window can open as soon as the server is full.

Lengths are lognormal (``median``, ``sigma``), clipped to
``[min, max]`` and, where ``buckets`` is given, rounded up to the next
bucket; gaps are exponential.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from collections.abc import Iterator

import numpy as np

__all__ = ["Req", "draw_lengths", "open_loop", "offline_queue", "seed_rng",
           "start_of", "prompt_lengths"]


@dataclasses.dataclass
class Req:
    """One request the generator offers."""

    key: tuple[int, int]        # (period or epoch, index): unique
    prompt_len: int
    out_len: int
    arrival: float | None       # seconds after the window opens (open)
    tokens: np.ndarray          # int32 prompt ids


def seed_rng(*entropy: int) -> np.random.Generator:
    """A generator for any whole numbers, however large or negative."""
    return np.random.default_rng([int(e) % (1 << 64) for e in entropy])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_lengths(spec: dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """``n`` lengths at the ``n`` mid-quantiles of a lognormal, clipped,
    rounded up to buckets, in an order drawn from ``rng``."""
    z = np.asarray([statistics.NormalDist().inv_cdf(q)
                    for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.searchsorted(b, x, side="left")]
    return x[rng.permutation(n)].astype(np.int64)


def start_of(seed: int, n: int) -> int:
    """Where in a cycle of ``n`` a seed's run starts."""
    return int(seed_rng(seed, 3).integers(n))


def _cycle_len(mix: dict, seconds: float) -> int:
    if mix["loop"] == "offline":
        return int(mix["base_requests"])
    return max(1, round(mix["rate_per_s"] * seconds))


def _cycle(mix: dict, n: int):
    """The cycle's prompt and output lengths, and the generator that
    goes on to draw its gaps."""
    base = seed_rng(mix["base_seed"])
    return (draw_lengths(mix["prompt"], n, base),
            draw_lengths(mix["output"], n, base), base)


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """Every prompt length a run of ``seconds`` can send."""
    return sorted(set(_cycle(mix, _cycle_len(mix, seconds))[0].tolist()))


def _tokens(seed: int, key: tuple[int, int], n: int, vocab: int
            ) -> np.ndarray:
    return seed_rng(seed, 7, key[0] + 1, key[1]).integers(
        0, vocab, n, dtype=np.int32)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> Iterator[Req]:
    """Requests in arrival order, from ``-lead_s`` on, without end.

    Arrival times are seconds after the window opens; the window is
    ``[0, seconds)`` and holds exactly ``round(rate * seconds)``."""
    n = _cycle_len(mix, seconds)
    prompts, outs, base = _cycle(mix, n)
    gaps = -np.log1p(-_quantiles(n))[base.permutation(n)]
    gaps *= seconds / gaps.sum()
    order = np.roll(np.arange(n), -start_of(seed, n))
    offsets = np.concatenate([[0.0], np.cumsum(gaps[order])[:-1]])
    lead = float(mix["lead_s"])
    period = -math.ceil(lead / seconds) if lead > 0 else 0
    while True:
        start = period * seconds + offsets
        for j in range(n):
            if start[j] < -lead:
                continue
            key = (period, j)
            p, o = int(prompts[order[j]]), int(outs[order[j]])
            yield Req(key, p, o, float(start[j]),
                      _tokens(seed, key, p, vocab))
        period += 1


def offline_queue(mix: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Requests in queue order, without end; the first ``slots`` are the
    staggered ones."""
    n = _cycle_len(mix, 0.0)
    prompts, outs, _ = _cycle(mix, n)
    order = np.roll(np.arange(n), -start_of(seed, n))
    slots = int(mix["slots"])
    epoch, served = 0, 0
    while True:
        for j in range(n):
            key = (epoch, j)
            p, o = int(prompts[order[j]]), int(outs[order[j]])
            if served < slots:
                o = max(1, math.ceil(o * (served + 1) / slots))
            served += 1
            yield Req(key, p, o, None, _tokens(seed, key, p, vocab))
        epoch += 1
