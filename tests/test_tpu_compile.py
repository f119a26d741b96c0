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



def _devtrace():
    """The on-chip benchmark's trace reduction (``chipbench/devtrace.py``),
    whose names tell prefill programs from decode programs."""
    import importlib.util
    import sys
    from pathlib import Path

    name = "chipbench_devtrace"
    if name not in sys.modules:
        path = Path(__file__).resolve().parents[1] / "chipbench" \
            / "devtrace.py"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod        # dataclasses resolve it by name
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.mark.parametrize("arch,slots,cap,pages", [
    ("granite-8b", 16, 2048, 1280),        # GQA 4:1, 8 KV heads of 128
    ("stablelm-1.6b", 16, 1536, 1152),     # MHA, 32 KV heads of 64
])
def test_paged_decode_step_compiles_with_the_kernel(one_chip, monkeypatch,
                                                    arch, slots, cap,
                                                    pages):
    """The serving decode step at a cell's attention shapes, two layers:
    paged attention is one Mosaic kernel, nothing of the capped span is
    widened to f32, and the kernel's names are none the benchmark takes
    for the prefill's flash kernel."""
    import dataclasses
    import re

    from repro.configs import build_model, get_config
    from repro.kernels import ops
    from repro.train.step import make_ctx

    monkeypatch.setenv("ADSALA_BACKEND", "pallas")
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret=None:
                        False)
    model = build_model(dataclasses.replace(get_config(arch), n_layers=2))
    ctx = make_ctx(None, "decode", cache_len=cap)
    table = cap // 16

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16)))
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_paged_cache(pages, 16, ctx, jnp.bfloat16)))

    def step(p, pool, tok, pos, tab):
        return model.decode_step(p, tok, pool, pos, ctx, tab)

    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((slots, 1), (slots,), (slots, table))]
    jitted = jax.jit(step, donate_argnums=(1,))
    hlo = jitted.lower(params, pool, *ints).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert not re.search(rf"f32\[{slots},{cap}[,\]]", hlo)

    def kernels(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["jaxpr"].debug_info.func_name
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from kernels(sub)

    names = list(kernels(jax.make_jaxpr(step)(params, pool, *ints).jaxpr))
    assert names == ["_paged_decode_kernel"]
    dt = _devtrace()
    for text in names + [line.split("=")[0] for line in calls]:
        assert not any(m in text for m in dt.FLASH_MARKS), text
        assert dt.FLASH_JIT not in text, text
