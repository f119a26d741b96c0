"""The scheduler's timestamps, its host spans under JAX's profiler, and
the program and kernel names that the device-trace reduction of the
on-chip benchmark matches."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_smoke_config
from repro.serve.scheduler import ContinuousBatchingScheduler

pytestmark = pytest.mark.timeout(300)

SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.seed_pages",
         "serve.decode")


@pytest.fixture(scope="module")
def built():
    cfg = get_smoke_config("stablelm-1.6b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _sched(built, slots=2):
    cfg, model, params = built
    return ContinuousBatchingScheduler(
        model, cfg, params, slots=slots, n_pages=24, page_size=4,
        max_seq_len=24)


def _requests(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab, int(rng.integers(3, 9))).tolist(),
             int(rng.integers(1, 6))) for _ in range(n)]


def test_timestamps_are_ordered(built):
    s = _sched(built)
    rids = [s.submit(p, m) for p, m in _requests(built[0])]
    finished = s.run_until_drained()
    assert sorted(finished) == sorted(rids)
    for f in finished.values():
        assert f.submitted_at <= f.admitted_at <= f.token_times[0]
        assert len(f.token_times) == len(f.tokens)
        assert all(a <= b for a, b in zip(f.token_times, f.token_times[1:]))
    # five requests through two slots: some waited in the queue for a
    # slot, and were admitted after an earlier one's first token
    first = min(finished.values(), key=lambda f: f.admitted_at)
    later = [f for f in finished.values() if f.admitted_step > 0]
    assert later
    assert all(f.admitted_at >= first.token_times[0] for f in later)


def _events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, dict(e.stats),
                                int(e.start_ns), int(e.duration_ns)))
    return out


# the profiler's stats type warns when Python first inspects it
@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_profiler_trace_holds_the_spans(built, tmp_path):
    cfg = built[0]
    s = _sched(built)
    s.submit([1, 2, 3], 2)
    s.run_until_drained()                # compile outside the trace
    reqs = _requests(cfg, n=3, seed=5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rids = [s.submit(p, max(m, 2)) for p, m in reqs]
        s.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    evs = _events(tmp_path)
    assert {n for n, *_ in evs} == set(SPANS)
    admits = [st for n, st, *_ in evs if n == "serve.admit"]
    assert sorted(st["rid"] for st in admits) == sorted(rids)
    for st in admits:
        f = s.finished[st["rid"]]
        assert st["prompt_len"] == len(f.prompt)
        assert st["queue_wait_ms"] == pytest.approx(
            1e3 * (f.admitted_at - f.submitted_at))
    for name, keys in (("serve.step", {"step", "active", "pending"}),
                       ("serve.prefill", {"rid", "prompt_len"}),
                       ("serve.seed_pages", {"rid", "pages"}),
                       ("serve.decode", {"active", "live_pages"})):
        stats = [st for n, st, *_ in evs if n == name]
        assert stats and all(set(st) == keys for st in stats), name
        assert all(isinstance(v, (int, float)) for st in stats
                   for v in st.values())
    # every active slot reads at least its first page, at most a row
    for st in (st for n, st, *_ in evs if n == "serve.decode"):
        assert st["active"] <= st["live_pages"] \
            <= st["active"] * s.table_pages
    # prefill and page seeding nest inside their request's admission
    spans = {(n, st.get("rid")): (t, t + d) for n, st, t, d in evs
             if n in ("serve.admit", "serve.prefill", "serve.seed_pages")}
    for rid in rids:
        lo, hi = spans[("serve.admit", rid)]
        for child in ("serve.prefill", "serve.seed_pages"):
            c0, c1 = spans[(child, rid)]
            assert lo <= c0 <= c1 <= hi


def test_decode_program_keeps_its_module_name(built):
    """The device-trace reduction tells the served programs apart by
    this module name."""
    s = _sched(built)
    lowered = s._decode.lower(
        s.params, s.pool, jnp.asarray(s._tok[:, None]),
        jnp.asarray(s._pos), jnp.asarray(s._table))
    assert "module @jit__lambda" in lowered.as_text()


@pytest.mark.parametrize("grid,kernel", [("tri", "_flash_tri_kernel"),
                                         ("dense", "_flash_dense_kernel")])
def test_flash_kernel_names_are_unchanged(grid, kernel):
    """The device-trace reduction finds the flash kernel by these
    names."""
    from repro.kernels.flash_attention import flash_attention_pallas

    def kernels(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["jaxpr"].debug_info.func_name
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from kernels(sub)

    q = jnp.ones((2, 128, 64), jnp.float32)
    closed = jax.make_jaxpr(lambda q: flash_attention_pallas(
        q, q, q, bq=64, bkv=64, interpret=True, grid=grid))(q)
    assert list(kernels(closed.jaxpr)) == [kernel]
