"""Run the harness's tests on the CPU, with the harness and the program
importable: ``python -m pytest chipbench/tests``."""

import dataclasses
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import spec  # noqa: E402


def smoke_cell(workload: str, root=spec.ROOT, **sizes):
    """A workload with its configuration cut by its family's ``smoke``
    (then by ``sizes``) and its mix cut to a size the CPU runs in
    seconds; everything else as the files say."""
    cell = spec.load_cell(workload, root)
    cfg = dict(cell.config)
    cfg.update(cell.family.smoke(cfg))
    cfg.update(sizes)
    mix_ = dict(cell.traffic)
    mix_.update(prompt=dict(mix_["prompt"], min=4, max=32, median=12,
                            buckets=[8, 16, 32]),
                output=dict(mix_["output"], min=2, max=12, median=5),
                slots=4, page_size=4, max_seq_len=48, pool_pages=48,
                check={"min_tokens": 20, "max_requests": 4})
    if mix_["loop"] == "open":
        mix_.update(rate_per_s=20.0, lead_s=0.5)
    else:
        mix_.update(queue_depth=4)
    return dataclasses.replace(cell, config=cfg, traffic=mix_)
