"""Find a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix. Each lives in a file
of its own under this directory: ``configs/<file>`` (named in
``BENCHMARK.json``), ``traffic/<mix>.json``, ``limits/<workload>.json``
(the limits of the comparison that decides ``correct``) and
``metrics/<metric>.py`` (one reader per per-layer metric). A new cell,
mix or metric is new files plus new entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

#: this directory, and the checkout that holds it
BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: the persistent compilation cache: a fixed path inside the checkout,
#: because the directory is part of every entry's key
JAX_CACHE = BENCH_DIR / ".cache" / "jax"


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with everything it names, read from its files."""

    name: str
    chips: int
    config: dict            # the configuration as run
    traffic: dict           # the traffic mix's parameters
    limits: dict            # {"logit_gap": ..., ...} for ``correct``
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reported_by(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH_DIR / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_by(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_by(m, workload)])


def dims(config: dict) -> dict:
    """The sizes the harness computes with, from a configuration file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "d": d,
        "ff": int(config["intermediate_size"]),
        "heads": h,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // h),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
        "rotary": float(config.get("partial_rotary_factor", 1.0)),
        "rope_theta": float(config["rope_theta"]),
        "norm": "layernorm" if "layer_norm_eps" in config else "rmsnorm",
        "norm_eps": float(config.get("layer_norm_eps",
                                     config.get("rms_norm_eps"))),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def program_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: the
    program's own entry for ``program_arch`` with the file's sizes, and a
    check that everything else the program fixes agrees with the file."""
    import dataclasses as dc

    from repro.configs import get_config

    k = dims(config)
    arch = dc.replace(
        get_config(config["program_arch"]), n_layers=k["layers"],
        d_model=k["d"], n_heads=k["heads"], n_kv_heads=k["kv_heads"],
        d_ff=k["ff"], vocab=k["vocab"], head_dim=None)
    want = {"resolved_head_dim": k["head_dim"],
            "rope_fraction": k["rotary"], "norm_kind": k["norm"],
            "tie_embeddings": k["tied"], "mlp_kind": "swiglu",
            "attn_kind": "gqa", "window": None, "qk_norm": False}
    got = {key: getattr(arch, key) for key in want}
    if got != want:
        raise ValueError(f"{config['name']}: the program's configuration "
                         f"{got} departs from the file's {want}")
    return arch
