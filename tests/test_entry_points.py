"""The entry points' chip-facing plumbing: the compile cache, the kernel
path each step takes, meshes, ``serve.main(argv)`` and ``chip_smoke.py``
off the chip."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE == os.path.join(ROOT, ".jax_cache")
    assert enable_compile_cache() == CHECKOUT_CACHE
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE


def test_compile_cache_env_sets_no_other_dir(monkeypatch, tmp_path,
                                             cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """A fresh process with the variable set caches there and nowhere
    else (JAX reads the variable itself at import)."""
    cache = tmp_path / "cache"
    before = (set(os.listdir(CHECKOUT_CACHE))
              if os.path.isdir(CHECKOUT_CACHE) else set())
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8))).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "PYTHONPATH": os.path.join(ROOT, "src")}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(cache)
    assert any(cache.iterdir())
    after = (set(os.listdir(CHECKOUT_CACHE))
             if os.path.isdir(CHECKOUT_CACHE) else set())
    assert after == before


@pytest.mark.parametrize("mode,meshed,want", [
    ("prefill", False, "auto"), ("decode", False, "auto"),
    ("train", False, "xla"), ("prefill", True, "xla"),
    ("decode", True, "xla"), ("train", True, "xla")])
def test_ctx_backend(mode, meshed, want):
    """Pallas kernels have no VJP and GSPMD cannot partition a Mosaic
    kernel: train and meshed steps take the XLA path."""
    from repro.launch.mesh import make_mesh
    from repro.train.step import make_ctx
    mesh = make_mesh((1, 1), ("data", "model")) if meshed else None
    assert make_ctx(mesh, mode).backend == want


def test_meshes_are_auto():
    from jax.sharding import AxisType

    from repro.dist.sharding import abstract_mesh
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    am = abstract_mesh({"pod": 2, "data": 16, "model": 16})
    assert dict(am.shape) == {"pod": 2, "data": 16, "model": 16}
    assert tuple(am.axis_types) == (AxisType.Auto,) * 3


def test_serve_main_argv_queue(monkeypatch, tmp_path):
    """``serve.main`` takes its argv and returns the queue's summary."""
    from repro.launch.serve import main
    # keep JAX's cache setting as it is in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    res = main(["--arch", "stablelm-1.6b", "--scale", "smoke", "--queue",
                "--requests", "3", "--prompt-len", "8",
                "--gen-tokens", "3", "--slots", "2", "--page-size", "4"])
    assert len(res["finished"]) == 3
    assert res["tokens"] == sum(len(f.tokens)
                                for f in res["finished"].values())
    # latencies come from the scheduler's timestamps
    assert res["queue_wait_ms"]["n"] == res["ttft_ms"]["n"] == 3
    assert res["itl_ms"]["n"] == res["tokens"] - 3
    for name in ("queue_wait_ms", "ttft_ms"):
        assert 0 <= res[name]["p50"] <= res[name]["p90"]
    assert res["ttft_ms"]["p50"] >= res["queue_wait_ms"]["p50"]


def test_chip_smoke_fails_without_a_chip():
    """Off the chip the smoke exits non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
