"""Suite bootstrap.

* Fast lane: ``pytest -m "not slow"`` skips the end-to-end install and
  subprocess-spawning distributed suites (the ``slow`` marker is
  registered in pyproject.toml).
* ``pytest-timeout`` is a declared test dependency; when it is
  missing, a SIGALRM fallback plugin
  (repro._compat.pytest_timeout_fallback) enforces the suite's
  ``--timeout`` / ``@pytest.mark.timeout`` budgets so a wedged
  subprocess test fails instead of hanging the lane.
"""

import dataclasses
import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "src"))

try:
    import pytest_timeout  # noqa: F401

    _timeout_fallback = None
except ModuleNotFoundError:
    from repro._compat import pytest_timeout_fallback as _timeout_fallback


def pytest_addoption(parser):
    if _timeout_fallback is not None:
        _timeout_fallback.addoption(parser)


def pytest_configure(config):
    if _timeout_fallback is not None:
        config.pluginmanager.register(_timeout_fallback,
                                      "timeout-fallback")


@dataclasses.dataclass
class InstallRun:
    """Everything a test needs from one shared install run."""

    dir: str
    cfg: object          # InstallConfig
    backend: object      # SimulatedBackend
    data: object         # GatheredData
    report: object       # InstallReport


@pytest.fixture(scope="session")
def tiny_artifact(tmp_path_factory) -> InstallRun:
    """One real, minimal-budget, mixed-routine install shared by
    test_tuner, test_system and the routine property tests — replacing
    the per-module ``install()`` runs that duplicated ~identical
    artifacts."""
    from repro.core import (InstallConfig, SimulatedBackend, gather_data,
                            install)

    d = tmp_path_factory.mktemp("tiny_artifact")
    cfg = InstallConfig(
        n_samples=48, repeats=2, tile_ids=(0, 3),
        models=("linear_regression", "decision_tree", "xgboost"),
        routines=("gemm", "syrk", "trsm", "attn"),
        grid_budget="small", cv_splits=3, seed=0)
    backend = SimulatedBackend(seed=0)
    data = gather_data(backend, cfg)
    report = install(backend, cfg, data=data, artifact_dir=str(d))
    return InstallRun(dir=str(d), cfg=cfg, backend=backend, data=data,
                      report=report)
