"""Run the harness's tests on the CPU, with the harness and the program
importable: ``python -m pytest chipbench/tests``."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import spec  # noqa: E402

#: smoke sizes for a configuration file: every width cut, depth 2
SMOKE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_hidden_layers": 2,
         "vocab_size": 256}


def smoke_cell(config: str, mix: str, workload: str, **sizes):
    """A cell with a configuration's and a mix's files cut to a size the
    CPU runs in seconds; everything else as the files say."""
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    kv = cfg["num_key_value_heads"] * 4 // cfg["num_attention_heads"]
    cfg.update(SMOKE, num_key_value_heads=max(1, kv))
    cfg.update(sizes)
    with open(os.path.join(BENCH, "traffic", f"{mix}.json")) as f:
        mix_ = json.load(f)
    mix_.update(prompt=dict(mix_["prompt"], min=4, max=32, median=12,
                            buckets=[8, 16, 32]),
                output=dict(mix_["output"], min=2, max=12, median=5),
                slots=4, page_size=4, max_seq_len=48, pool_pages=48,
                check={"min_tokens": 20, "max_requests": 4})
    if mix_["loop"] == "open":
        mix_.update(rate_per_s=20.0, lead_s=0.5)
    else:
        mix_.update(queue_depth=4)
    bench = spec.load_benchmark()
    pick = lambda ms: [m for m in ms
                       if "workloads" not in m or workload in m["workloads"]]
    with open(os.path.join(BENCH, "limits", f"{workload}.json")) as f:
        limits = json.load(f)
    return spec.Cell(workload, 1, cfg, mix_, limits,
                     pick(bench["end_to_end"]), pick(bench["per_layer"]))
