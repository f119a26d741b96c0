"""Serving substrate: prefill/decode builders, cache sharding, the
paged-KV continuous-batching scheduler, and the drift-triggered online
re-install loop.

``repro.serve.step`` / ``scheduler`` pull in jax; the
re-install manager below is jax-free on purpose (it runs against the
simulated/measured timing backends), so it is safe to re-export
eagerly — the jax-backed names resolve lazily.
"""

from repro.serve.reinstall import (
    DriftTrigger,
    ReinstallConfig,
    ReinstallManager,
)

__all__ = ["DriftTrigger", "ReinstallConfig", "ReinstallManager",
           "ContinuousBatchingScheduler", "Request", "FinishedSeq",
           "PageAllocator"]

_LAZY = {
    "ContinuousBatchingScheduler": "repro.serve.scheduler",
    "Request": "repro.serve.scheduler",
    "FinishedSeq": "repro.serve.scheduler",
    "PageAllocator": "repro.serve.kv_cache",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
