"""Find a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix. Each lives in a file
of its own under this directory: ``configs/<file>`` (named in
``BENCHMARK.json``), ``traffic/<mix>.json``, ``limits/<workload>.json``
(the limits of the comparison that decides ``correct``) and
``metrics/<metric>.py`` (one reader per per-layer metric). A new cell,
mix or metric is new files plus new entries; nothing here changes.

A configuration names its architecture's family (``"family"``), which
``families/<family>.py`` defines: ``dims``, ``program_config``,
``program_params``, ``Reference``, ``decode_flops``, ``prefill_flops``
and ``smoke`` (``families/__init__.py`` says what each is). So a
configuration of a new architecture is also new files, a family module
and its reference under ``reference/``, plus entries.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from types import ModuleType

import families

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
    family: ModuleType      # families/<config["family"]>.py
    traffic: dict           # the traffic mix's parameters
    limits: dict            # {"logit_gap": ..., ...} for ``correct``
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @property
    def k(self) -> dict:
        """The sizes the harness computes with (the family's ``dims``)."""
        return self.family.dims(self.config)


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reported_by(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """A workload of the checkout at ``root``, with its files found under
    that checkout's copy of this directory."""
    bench = load_benchmark(root)
    here = root / BENCH_DIR.relative_to(ROOT)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(here / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(here / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=families.load(config.get("family"), here / "families"),
        traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_by(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_by(m, workload)])
