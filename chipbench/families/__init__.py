"""Architecture families: what the harness knows of one architecture.

A configuration file names its family (``"family": "dense"``), and
``families/<family>.py`` defines for every configuration of it:

* ``dims(config) -> k``: the sizes the harness computes with.
  ``k["vocab"]`` is what the traffic draws tokens from; every other key
  is the family's own.
* ``program_config(config)``: the program's ``ArchConfig``, with a check
  that the program agrees with the file.
* ``program_params(model, k, seed, dtype)``: the program's parameter
  tree, drawn on the device from the seed (``weights.draw``) and checked
  against ``model.init``.
* ``Reference(k, seed, dtype)``: the plain float32 reference at the
  highest precision; ``logits(tokens, fp8=False, length=0)``, with
  ``fp8=True`` the control (``reference/common.py``).
* ``decode_flops(k, ctx)``, ``prefill_flops(k, n)``: the operations of
  the model's mathematics at the live context.
* ``smoke(config)``: the configuration keys that cut it to a size the
  CPU tests run in seconds.

A family is loaded by its path, as ``run.read_metric`` loads a reader,
so a new architecture is a new module here, its reference under
``reference/``, and a configuration file that names it.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType

__all__ = ["DIR", "REQUIRED", "known", "load"]

DIR = Path(__file__).resolve().parent

#: the names every family module defines
REQUIRED = ("dims", "program_config", "program_params", "Reference",
            "decode_flops", "prefill_flops", "smoke")


def known(where: Path = DIR) -> list[str]:
    """The families a directory holds."""
    return sorted(p.stem for p in where.glob("*.py") if p.stem != "__init__")


def load(name: str, where: Path = DIR) -> ModuleType:
    """``<where>/<name>.py``, once it is known to define every name in
    :data:`REQUIRED`."""
    if name not in known(where):
        raise ValueError(f"unknown family {name!r}: the families are "
                         f"{known(where)} (in {where})")
    path = where / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    missing = [n for n in REQUIRED if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"family {name!r} ({path}) does not define "
                         f"{missing}; a family defines {list(REQUIRED)}")
    return mod
