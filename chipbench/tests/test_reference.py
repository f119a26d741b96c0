"""The plain reference against the program at smoke size on the CPU:
LayerNorm, partial rotary and MHA (stablelm), RMSNorm, GQA and full
rotary (granite), through the scheduler's prefill and paged decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from conftest import smoke_cell
from reference.common import served_gaps
from repro.configs import build_model
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.train.step import make_ctx

CELLS = ["stablelm-1.6b.chat", "granite-8b.code"]


@pytest.fixture(scope="module", params=CELLS,
                ids=lambda w: w.rsplit(".", 1)[0])
def served(request):
    """Float32 weights from seed 3 in both; four ragged requests through
    the scheduler (2 slots, pages of 4, so slots and pages are reused)."""
    cell = smoke_cell(request.param)
    k, fam = cell.k, cell.family
    arch = fam.program_config(cell.config)
    model = build_model(arch)
    params = fam.program_params(model, k, 3, jnp.float32)
    sched = ContinuousBatchingScheduler(
        model, arch, params, slots=2, n_pages=24, page_size=4,
        max_seq_len=40, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    reqs = {}
    for n, new in [(13, 9), (5, 12), (21, 3), (8, 7)]:
        toks = rng.integers(0, k["vocab"], n).astype(np.int32)
        reqs[sched.submit(toks.tolist(), new)] = toks
    fin = sched.run_until_drained()
    ref = fam.Reference(k, 3, dtype=jnp.float32)
    return k, model, params, ref, reqs, fin


def test_prefill_logits_match(served):
    k, model, params, ref, reqs, _ = served
    for toks in reqs.values():
        ctx = make_ctx(None, "prefill", cache_len=len(toks), remat=False)
        got, _ = model.prefill(params, jnp.asarray(toks[None]), ctx)
        want = ref.logits(toks)[len(toks) - 1]
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_every_served_token_is_the_reference_best(served):
    _, _, _, ref, reqs, fin = served
    for rid, toks in reqs.items():
        out = np.asarray(fin[rid].tokens, np.int32)
        g = served_gaps(ref, toks, out)["served"]
        assert len(g) == len(out)
        assert g.max() < 1e-4, (rid, g)


def test_the_reference_sees_a_wrong_token(served):
    _, _, _, ref, reqs, fin = served
    rid, toks = next(iter(reqs.items()))
    out = np.asarray(fin[rid].tokens, np.int32)
    out[2] = (out[2] + 1) % ref.k["vocab"]
    g = served_gaps(ref, toks, out)["served"]
    assert g[2] > 1e-2


def test_vmapped_layers_draw_what_one_layer_draws():
    cell = smoke_cell(CELLS[1])
    k, draw_layer = cell.k, cell.family.draw_layer
    key = weights.seed_key(2**40 + 5)
    stacked = jax.vmap(lambda l: draw_layer(
        key, k, l, jnp.bfloat16))(jnp.arange(k["layers"]))
    one = draw_layer(key, k, 1, jnp.bfloat16)
    for name in one:
        np.testing.assert_array_equal(np.asarray(stacked[name][1]),
                                      np.asarray(one[name]))
