"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

The TPU compiler is installed wherever libtpu is, and compiles for a
chip that is described rather than attached: each case lowers a kernel
at the main path's real widths with ``interpret=False`` and compiles it
for one chip of a described ``v5e:2x2`` topology.  Mosaic refuses here
what the interpreter accepts (misaligned blocks, VMEM over the scoped
limit), at no chip time.  Nothing runs, so these say nothing about
results or speed.

Only one process at a time may load libtpu, so the topology is
described inside a module fixture — never at import — and every case
stays in this one file, which a single worker runs.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core.costmodel import DEFAULT_TILES, EXTENDED_TILES, FLASH_BLOCKS
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.matmul import matmul_pallas

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache, topologies

    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache off here
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no libtpu
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for one described chip; return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("tile", [DEFAULT_TILES[0], EXTENDED_TILES[-1]],
                         ids=lambda t: "x".join(map(str, t)))
def test_matmul_compiles(one_chip, tile, dtype):
    """stablelm's MLP GEMM (2048 x 2048 @ 2048 x 5632) at two tiles."""
    bm, bk, bn = tile
    dt = DTYPES[dtype]
    hlo = _compile(
        lambda a, b: matmul_pallas(a, b, bm=bm, bk=bk, bn=bn),
        one_chip, ((2048, 2048), dt), ((2048, 5632), dt))
    assert "tpu_custom_call" in hlo


def test_grouped_matmul_compiles(one_chip):
    """8 mixtral experts (d=6144, ff=16384) over 128-row buckets."""
    bm, bk, bn = DEFAULT_TILES[3]
    hlo = _compile(
        lambda x, w: grouped_matmul_pallas(x, w, bm=bm, bk=bk, bn=bn),
        one_chip, ((8, 128, 6144), jnp.bfloat16),
        ((8, 6144, 16384), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("shape", [(32, 2048, 64), (32, 300, 64),
                                   (32, 2048, 128)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("grid", ["dense", "tri"])
def test_flash_attention_compiles(one_chip, grid, shape, dtype):
    """stablelm's 32 heads of 64 at a 2k prompt and at a ragged one (the
    clamped 300-row Q block is no multiple of 8), and 128-wide heads."""
    dt = DTYPES[dtype]
    hlo = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               grid=grid),
        one_chip, (shape, dt), (shape, dt), (shape, dt))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("grid", ["dense", "tri"])
@pytest.mark.parametrize("block", FLASH_BLOCKS,
                         ids=lambda b: "x".join(map(str, b)))
def test_flash_blocks_compile_at_ragged_prompt(one_chip, block, grid):
    """Every flash block the tuner may pick, at a ragged served prompt
    length (the serving queue draws prompts of 128-512 tokens)."""
    bq, bkv = block
    shape = (32, 310, 64)
    hlo = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               bq=bq, bkv=bkv, grid=grid),
        one_chip, (shape, jnp.float32), (shape, jnp.float32),
        (shape, jnp.float32))
    assert "tpu_custom_call" in hlo

