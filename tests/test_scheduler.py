"""Golden parity: continuous batching may reorder work, never results.

Every sequence decoded through the continuous-batching scheduler (paged
KV cache, ragged admission, slot reuse) must produce token-for-token
identical output to the fixed-batch ``prefill`` + ``decode_step`` path
on the same params — for both attention families (MHA and GQA KV
caches, MLA latent cache), untuned on both sides so the comparison is
pure cache plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_smoke_config
from repro.models.paged_cache import gather_pages
from repro.serve.kv_cache import pages_for
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.train.step import make_ctx

pytestmark = pytest.mark.timeout(300)

#: the attention families with a paged cache representation: MHA, GQA
#: and MLA
ARCHS = ["stablelm-1.6b", "granite-8b", "deepseek-v2-236b"]

_BUILT: dict = {}


def _built(arch):
    if arch not in _BUILT:
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _BUILT[arch] = (cfg, model, params)
    return _BUILT[arch]


def _trace(cfg, n=6, seed=7):
    """A fixed ragged request trace: (prompt, max_new) pairs."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        length = int(rng.integers(3, 10))
        out.append((rng.integers(0, cfg.vocab, length).tolist(),
                    int(rng.integers(1, 7))))
    return out


def _reference(model, cfg, params, prompt, max_new, cache_len):
    """The existing fixed-batch serving path, batch of one."""
    pctx = make_ctx(None, "prefill", cache_len=cache_len, remat=False)
    dctx = make_ctx(None, "decode", cache_len=cache_len)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, cache = model.prefill(params, toks, pctx)
    out = [int(jnp.argmax(logits[0]))]
    for i in range(max_new - 1):
        logits, cache = model.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache,
            jnp.int32(len(prompt) + i), dctx)
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_golden_parity_vs_fixed_batch(arch):
    cfg, model, params = _built(arch)
    page = 4
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=3, n_pages=32, page_size=page,
        max_seq_len=16)
    trace = _trace(cfg)
    rids = [sched.submit(p, n) for p, n in trace]
    finished = sched.run_until_drained()

    assert len(finished) == len(trace)
    # the reference decodes against the same gathered span (cap) so the
    # attention mask geometry matches slot-for-slot
    for rid, (prompt, max_new) in zip(rids, trace):
        want = _reference(model, cfg, params, prompt, max_new, sched.cap)
        assert list(finished[rid].tokens) == want, \
            f"{arch} rid={rid} prompt_len={len(prompt)}"
    # all pages returned to the pool
    sched.alloc.check()
    assert sched.alloc.live_pages == 0


@pytest.mark.parametrize("arch", ARCHS)
def test_slot_reuse_does_not_cross_contaminate(arch):
    """The same prompt admitted early and late (through recycled pages
    and slots) must decode identically — stale page contents from a
    retired sequence can never leak into a new one."""
    cfg, model, params = _built(arch)
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=2, n_pages=12, page_size=4,
        max_seq_len=12)
    rng = np.random.default_rng(3)
    probe = rng.integers(0, cfg.vocab, 5).tolist()
    first = sched.submit(probe, 4)
    fillers = [sched.submit(rng.integers(0, cfg.vocab,
                                         int(rng.integers(3, 9))).tolist(),
                            int(rng.integers(2, 6))) for _ in range(3)]
    again = sched.submit(probe, 4)      # admitted after retires/recycling
    finished = sched.run_until_drained()
    assert finished[first].tokens == finished[again].tokens
    assert len(finished) == len(fillers) + 2


@pytest.mark.parametrize("arch", ARCHS)
def test_seed_paged_cache_holds_the_prefill_rows(arch):
    """``LM.seed_paged_cache`` writes one prompt's prefill cache into
    the sequence's pages: gathering them through its page table gives
    back the contiguous cache's rows for every leaf and layer, prefix
    and scan segments alike, and no other page is written."""
    cfg, model, params = _built(arch)
    page, n_pages = 4, 16
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 7)
    cache_len = pages_for(len(prompt), page) * page
    pctx = make_ctx(None, "prefill", cache_len=cache_len, remat=False)
    _, cache = model.prefill(
        params, jnp.asarray(prompt[None], jnp.int32), pctx)
    pool = model.init_paged_cache(n_pages, page,
                                  make_ctx(None, "decode"), jnp.float32)
    pages = [11, 3]                       # out of order, not from 0
    pool = model.seed_paged_cache(pool, cache,
                                  jnp.asarray(pages, jnp.int32))
    table = jnp.asarray([pages], jnp.int32)
    others = np.setdiff1d(np.arange(n_pages), pages)

    checked = {}
    for seg in ("prefix", "scan", "suffix"):
        pools = jax.tree.leaves(pool[seg])
        rows = jax.tree.leaves(cache[seg])
        assert len(pools) == len(rows), seg
        for pl, rl in zip(pools, rows):
            if seg == "scan":
                assert pl.shape[0] == rl.shape[0] == model.repeats
                for layer in range(model.repeats):
                    got = gather_pages(pl, table, jnp.int32(layer))
                    np.testing.assert_array_equal(
                        got[0], rl[layer, 0].reshape(cache_len, -1))
                assert not np.asarray(pl)[:, others].any(), seg
            else:
                got = gather_pages(pl, table)
                np.testing.assert_array_equal(
                    got[0], rl[0].reshape(cache_len, -1))
                assert not np.asarray(pl)[others].any(), seg
        checked[seg] = len(pools)
    assert checked["scan"] > 0
    assert (checked["prefix"] > 0) == bool(model.prefix)
    assert (checked["suffix"] > 0) == bool(model.suffix)


def test_trsm_site_tags_survive_paging():
    """The paged cache update keeps the fixed-batch path's TRSM-site
    recorder tag — the signal the workload profile / re-installer keys
    on must not change shape because serving went paged."""
    cfg, model, params = _built("stablelm-1.6b")
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=2, n_pages=16, page_size=4,
        max_seq_len=12)
    sched.submit([1, 2, 3, 4, 5], 3)
    sched.run_until_drained()
    decode_sites = {e.site for e in sched.recorders["decode"].events}
    assert "attn.cache_update" in decode_sites
    trsm = [e for e in sched.recorders["decode"].events
            if e.site == "attn.cache_update"]
    assert all(e.routine == "trsm" for e in trsm)
    # cache-update events price the gathered span, not the pool size
    assert all(e.m == sched.cap for e in trsm)
    assert sched.recorders["prefill"].events, "prefill traffic unrecorded"


def test_mla_cache_update_tag():
    cfg, model, params = _built("deepseek-v2-236b")
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=1, n_pages=8, page_size=4,
        max_seq_len=12)
    sched.submit([1, 2, 3], 2)
    sched.run_until_drained()
    sites = {e.site for e in sched.recorders["decode"].events}
    assert "mla.cache_update" in sites


def test_admission_defers_then_completes_under_tiny_pool():
    """A pool that fits one sequence at a time forces FIFO deferral;
    everything still finishes with zero drops."""
    cfg, model, params = _built("stablelm-1.6b")
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=4, n_pages=3, page_size=4,
        max_seq_len=12)
    rng = np.random.default_rng(11)
    rids = [sched.submit(rng.integers(0, cfg.vocab, 6).tolist(), 4)
            for _ in range(4)]
    finished = sched.run_until_drained()
    assert sorted(finished) == sorted(rids)
    # one 6+3-token sequence needs 3 pages = the whole pool: strictly
    # sequential service, so later sequences were admitted later
    admits = [finished[r].admitted_step for r in rids]
    assert admits == sorted(admits) and len(set(admits)) == len(admits)


def test_max_new_one_finishes_at_prefill():
    cfg, model, params = _built("stablelm-1.6b")
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=1, n_pages=8, page_size=4,
        max_seq_len=12)
    rid = sched.submit([5, 6, 7], 1)
    finished = sched.run_until_drained()
    assert len(finished[rid].tokens) == 1
    assert sched.steps == 0             # never needed a decode step
    assert finished[rid].tokens[0] == _reference(
        model, cfg, params, [5, 6, 7], 1, sched.cap)[0]


def test_submit_validation():
    cfg, model, params = _built("stablelm-1.6b")
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=1, n_pages=4, page_size=4,
        max_seq_len=8)
    with pytest.raises(ValueError, match="cap"):
        sched.submit(list(range(7)), 4)     # 10 slots > cap 8
    with pytest.raises(ValueError, match="empty"):
        sched.submit([], 2)
    with pytest.raises(ValueError, match="max_new"):
        sched.submit([1, 2], 0)
    rid = sched.submit([1, 2], 2)
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit([3, 4], 2, rid=rid)


def test_unpageable_families_refuse_loudly():
    """Ring/recurrent caches have no paged form: the scheduler must
    raise at construction, not corrupt at decode."""
    cfg = get_smoke_config("recurrentgemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        ContinuousBatchingScheduler(model, cfg, params, slots=1,
                                    n_pages=4, page_size=4,
                                    max_seq_len=8)

    wcfg = get_smoke_config("whisper-tiny")
    wmodel = build_model(wcfg)
    wparams = wmodel.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        ContinuousBatchingScheduler(wmodel, wcfg, wparams, slots=1,
                                    n_pages=4, page_size=4,
                                    max_seq_len=8)
