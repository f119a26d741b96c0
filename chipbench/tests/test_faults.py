"""A run with its timed path broken must come out not correct.

Each test drives the whole of a run (set-up, window, reference check)
at smoke size on the CPU, skipping only the harness's look for a chip,
with one fault planted in the scheduler the window drives:

* ``token``: every decoded token altered where it is produced;
* ``state``: the decode step returns its page pool unchanged, so no
  decoded token's keys and values reach the cache;
* ``half``: half of the decode slots get the other half's logits.

The sound run beside them must come out correct under the same limit.
"""

import jax
import jax.numpy as jnp
import pytest

import run
from conftest import smoke_cell

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _token(sched):
    inner = sched._decode

    def decode(*a):
        logits, pool = inner(*a)
        return jnp.roll(logits, 1, axis=-1), pool
    sched._decode = decode


def _state(sched):
    model, ctx = sched.model, sched._dctx
    sched._decode = jax.jit(
        lambda p, pool, tok, pos, table: (
            model.decode_step(p, tok, pool, pos, ctx, table)[0], pool))


def _half(sched):
    inner = sched._decode

    def decode(*a):
        logits, pool = inner(*a)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), pool
    sched._decode = decode


class Faulty(run.Session):
    def __init__(self, cell, fault):
        super().__init__(cell, tuner=False)
        self.fault = fault

    def scheduler(self, params):
        sched = super().scheduler(params)
        if self.fault:
            self.fault(sched)
        return sched


CASES = ["stablelm-1.6b.chat", "granite-8b.code"]


@pytest.mark.parametrize("fault", [None, _token, _state, _half],
                         ids=["sound", "token", "state", "half"])
@pytest.mark.parametrize("case", CASES, ids=lambda w: w.rsplit(".", 1)[1])
def test_a_broken_path_is_not_correct(monkeypatch, case, fault):
    monkeypatch.setattr(run, "_peaks", lambda dev: PEAKS)
    cell = smoke_cell(case)
    # every slot busy, and enough of the served requests compared that
    # some of them decoded in each half of the slots
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 80.0
    cell.traffic["check"] = {"min_tokens": 80, "max_requests": 24}
    res = run.run_cell(cell, 2**33 + 1, 2.0, False,
                       session=Faulty(cell, fault), t_start=0.0)
    gap = res["checks"]["logit_gap"]
    assert res["compared_tokens"] >= 50
    assert res["correct"] is (fault is None), gap
