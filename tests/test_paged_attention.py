"""The Pallas paged decode kernel against the XLA path of
``attention_decode_paged``, in the Pallas interpreter on the CPU.

The kernel reads only each slot's live pages and sums the softmax block
by block; the XLA path gathers the whole capped span. They compute the
same f32 arithmetic in another order, so outputs agree to the dtype's
rounding, and the cache update (shared code) is bitwise equal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_smoke_config
from repro.models.layers import AttnSpec, attention_decode_paged
from repro.serve.kv_cache import HOLE, PagedKV, pages_for
from repro.serve.scheduler import ContinuousBatchingScheduler

pytestmark = pytest.mark.timeout(300)

PAGE = 16
TABLE_PAGES = 8                      # cap = 128 token slots
CAP = PAGE * TABLE_PAGES
N_PAGES = 40

#: (heads, KV heads, head width): MHA with 64-wide heads (stablelm's
#: shape) and GQA 4:1 with 128-wide heads (granite's)
SHAPES = {"mha-dh64": (4, 4, 64), "gqa4-dh128": (8, 2, 128)}
#: f32 rounding of two summation orders; one bf16 rounding of the output
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2 ** -7}


def _layer(shape, dtype, lengths, seed=0):
    """One attention layer, a pool holding random rows, and a batch whose
    slot ``i`` holds ``lengths[i]`` tokens after this step's append (0 =
    inactive slot), its pages scattered over the pool in a scrambled
    order and every entry past its live prefix a hole."""
    h, kvh, dh = shape
    s = AttnSpec(d_model=h * dh, n_heads=h, n_kv_heads=kvh, head_dim=dh,
                 rope_fraction=0.0)
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    d = s.d_model
    p = {"wq": jax.random.normal(keys[0], (d, h * dh)) * d ** -0.5,
         "wk": jax.random.normal(keys[1], (d, kvh * dh)) * d ** -0.5,
         "wv": jax.random.normal(keys[2], (d, kvh * dh)) * d ** -0.5,
         # the identity puts the attention output itself in the result
         "wo": jnp.eye(h * dh)}
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    row = (N_PAGES, PAGE, kvh * dh)
    pool = PagedKV(jnp.asarray(rng.normal(size=row), dtype),
                   jnp.asarray(rng.normal(size=row), dtype))
    table = np.full((len(lengths), TABLE_PAGES), HOLE, np.int32)
    free = list(rng.permutation(N_PAGES))
    for i, n in enumerate(lengths):
        for j in range(pages_for(n, PAGE)):
            table[i, j] = free.pop()
    pos = np.asarray(lengths, np.int32) - 1          # -1: inactive
    x = jax.random.normal(keys[3], (len(lengths), 1, d)).astype(dtype)
    return p, x, s, pool, jnp.asarray(table), jnp.asarray(pos)


_CASES = ([(shape, jnp.float32, n) for shape in SHAPES
           for n in (1, 15, 16, 17, CAP)]
          + [(shape, jnp.bfloat16, n) for shape in SHAPES
             for n in (17, CAP)])


@pytest.mark.parametrize(
    "shape,dtype,length", _CASES,
    ids=[f"{s}-{jnp.dtype(d).name}-len{n}" for s, d, n in _CASES])
def test_kernel_matches_xla_path(shape, dtype, length):
    """Slot 0 holds ``length`` tokens, slot 1 is inactive (``pos ==
    -1``), slot 2 holds 33 (a partial third page)."""
    lengths = [length, 0, 33]
    p, x, s, pool, table, pos = _layer(SHAPES[shape], dtype, lengths)
    want, want_pool = attention_decode_paged(p, x, s, pool, table, pos,
                                             backend="xla")
    got, got_pool = attention_decode_paged(p, x, s, pool, table, pos,
                                           backend="pallas")
    for a, b in zip(jax.tree.leaves(got_pool), jax.tree.leaves(want_pool)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    # the inactive slot reads nothing and gives a finite zero
    np.testing.assert_array_equal(got[1], 0.0)
    live = [0, 2]
    tol = TOL[dtype]
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


def _serve(arch, backend, monkeypatch):
    monkeypatch.setenv("ADSALA_BACKEND", backend)
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=3, n_pages=48, page_size=4,
        max_seq_len=24)
    rng = np.random.default_rng(11)
    rids = [sched.submit(rng.integers(0, cfg.vocab,
                                      int(rng.integers(3, 12))).tolist(),
                         int(rng.integers(2, 10)))
            for _ in range(6)]
    finished = sched.run_until_drained()
    return [finished[r].tokens for r in rids]


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-8b"])
def test_scheduler_decodes_the_same_tokens_with_the_kernel(arch,
                                                           monkeypatch):
    """The smoke configs served with the Pallas kernels (interpreted
    here) decode token for token what the XLA path decodes."""
    assert _serve(arch, "pallas", monkeypatch) \
        == _serve(arch, "xla", monkeypatch)


def test_kernel_ignores_stale_vmem_and_races_nothing():
    """In the TPU interpreter with every scratch buffer born NaN and its
    DMA race detector on, rows past a slot's live pages (stale VMEM)
    never reach the output, and no copy races a read."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.paged_attention import paged_decode_attention_pallas

    h, kvh, dh = SHAPES["gqa4-dh128"]
    lengths = [17, 0, CAP, 33]
    _, _, _, pool, table, pos = _layer((h, kvh, dh), jnp.float32, lengths)
    q = jax.random.normal(jax.random.PRNGKey(3), (len(lengths), h, dh))
    params = pltpu.InterpretParams(uninitialized_memory="nan",
                                   detect_races=True)
    got = paged_decode_attention_pallas(
        q, pool.k, pool.v, jnp.maximum(pos + 1, 0), table,
        interpret=params)
    assert not interpret_pallas_call.races.races_found
    want = paged_decode_attention_pallas(
        q, pool.k, pool.v, jnp.maximum(pos + 1, 0), table, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
