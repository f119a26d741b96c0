"""The control must come out not correct.

The control is the reference computed in float8 e4m3 (``fp8=True``),
the precision below the bfloat16 the cells serve in, read at the served
positions of a sound run: the gap of the token it puts first. At the
cell's own size it is read on the chip (``calibrate.py readings
--control``, PERF.md); here it is read at a size the CPU runs, wide
enough that float8 rounding moves the logits. Standing in for the served
tokens, it must fail the run's ``correct`` under the cell's limit, while
the program, in bfloat16, stays within it.
"""

import pytest

import run
from conftest import smoke_cell

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}

CASES = ["stablelm-1.6b.chat", "granite-8b.code"]


@pytest.mark.parametrize("case", CASES, ids=lambda w: w.rsplit(".", 1)[1])
def test_the_fp8_control_fails_the_limit(monkeypatch, case):
    monkeypatch.setattr(run, "_peaks", lambda dev: PEAKS)
    cell = smoke_cell(case, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, vocab_size=2048)
    cell.traffic["check"] = {"min_tokens": 200, "max_requests": 64}
    res = run.run_cell(cell, 5, 3.0, False,
                       session=run.Session(cell, tuner=False),
                       control=True, t_start=0.0)
    limit = cell.limits["logit_gap"]
    assert res["compared_tokens"] >= 200
    assert res["program_gap"] <= limit, (res["program_gap"], limit)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["logit_gap"]["value"] > limit, res["checks"]
