"""Property tests for the BLAS-3 routine cost model and tuner plumbing."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ROUTINES, AdsalaTuner, candidate_configs
from repro.core.costmodel import (
    GemmConfig,
    TPUSpec,
    TRSM_SEQ_CHIPS,
    estimate_batch_terms,
    estimate_routine_time,
    routine_ids,
)

_CFGS = [GemmConfig(c, p, t) for c in (1, 2, 4, 8, 64, 512)
         for p in ("M", "N", "K", "2D") for t in (0, 3, 5)
         if not (p == "2D" and c < 4)]


def _terms(tb):
    return (tb.compute_s, tb.memory_s, tb.collective_s, tb.launch_s)


# ---------------------------------------------------------------------------
# batched == scalar, bit for bit (noise-free), for every routine
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(m=st.integers(8, 65536), k=st.integers(8, 65536),
       n=st.integers(8, 65536),
       routine=st.sampled_from(ROUTINES))
def test_batch_matches_scalar_bitwise_per_routine(m, k, n, routine):
    bb = estimate_batch_terms(np.array([[m, k, n]]), _CFGS,
                              routines=routine)
    for j, cfg in enumerate(_CFGS):
        tb = estimate_routine_time(m, k, n, cfg, routine=routine)
        assert bb.compute_s[0, j] == tb.compute_s
        assert bb.memory_s[0, j] == tb.memory_s
        assert bb.collective_s[0, j] == tb.collective_s
        assert bb.launch_s[0, j] == tb.launch_s


def test_batch_matches_scalar_bitwise_mixed_rows():
    """Rows mixing all three routines in one grid call."""
    rng = np.random.default_rng(9)
    dims = np.stack([rng.integers(8, 65536, 30) for _ in range(3)],
                    axis=1).astype(np.int64)
    routines = [ROUTINES[i % 3] for i in range(len(dims))]
    bb = estimate_batch_terms(dims, _CFGS, routines=routines)
    for i, (m, k, n) in enumerate(dims):
        for j, cfg in enumerate(_CFGS):
            tb = estimate_routine_time(int(m), int(k), int(n), cfg,
                                       routine=routines[i])
            assert bb.compute_s[i, j] == tb.compute_s
            assert bb.memory_s[i, j] == tb.memory_s
            assert bb.collective_s[i, j] == tb.collective_s
            assert bb.launch_s[i, j] == tb.launch_s


def test_batch_matches_scalar_under_custom_spec_all_routines():
    spec = TPUSpec(vmem_bytes=2**16, peak_flops=90e12, mxu_dim=256)
    rng = np.random.default_rng(3)
    dims = np.stack([rng.integers(8, 4096, 12) for _ in range(3)],
                    axis=1).astype(np.int64)
    routines = [ROUTINES[i % 3] for i in range(len(dims))]
    bb = estimate_batch_terms(dims, _CFGS, spec, routines=routines)
    for i, (m, k, n) in enumerate(dims):
        for j, cfg in enumerate(_CFGS):
            tb = estimate_routine_time(int(m), int(k), int(n), cfg, spec,
                                       routine=routines[i])
            assert bb.total_s[i, j] == tb.total_s


# ---------------------------------------------------------------------------
# physics sanity per routine
# ---------------------------------------------------------------------------

@settings(max_examples=18, deadline=None)
@given(m=st.integers(8, 16384), k=st.integers(8, 16384),
       n=st.integers(8, 16384),
       routine=st.sampled_from(ROUTINES),
       cfg=st.sampled_from(_CFGS))
def test_terms_positive_and_finite_all_routines(m, k, n, routine, cfg):
    tb = estimate_routine_time(m, k, n, cfg, routine=routine)
    for v in _terms(tb):
        assert np.isfinite(v) and v >= 0
    assert tb.total_s > 0


@settings(max_examples=18, deadline=None)
@given(m=st.integers(8, 16384), k=st.integers(8, 16384),
       n=st.integers(8, 16384), cfg=st.sampled_from(_CFGS))
def test_syrk_flops_at_most_gemm(m, k, n, cfg):
    """Triangular output: SYRK never computes more than the same-shape
    GEMM (issue acceptance: SYRK flops <= GEMM flops)."""
    syrk = estimate_routine_time(m, k, n, cfg, routine="syrk")
    gemm = estimate_routine_time(m, k, n, cfg, routine="gemm")
    assert syrk.compute_s <= gemm.compute_s


@settings(max_examples=12, deadline=None)
@given(m=st.integers(64, 16384), k=st.integers(8, 4096),
       n=st.integers(8, 4096),
       p=st.sampled_from([8, 16, 64, 512]))
def test_trsm_m_parallelism_capped(m, k, n, p):
    """Chips beyond TRSM_SEQ_CHIPS on the M axis buy no compute time:
    the substitution chain serialises them."""
    at_cap = estimate_routine_time(
        m, k, n, GemmConfig(TRSM_SEQ_CHIPS, "M", 3), routine="trsm")
    beyond = estimate_routine_time(m, k, n, GemmConfig(p, "M", 3),
                                   routine="trsm")
    assert beyond.compute_s == at_cap.compute_s


def test_batch_noise_positive_finite_all_routines():
    rng = np.random.default_rng(4)
    dims = np.stack([rng.integers(8, 65536, 24) for _ in range(3)],
                    axis=1).astype(np.int64)
    routines = [ROUTINES[i % 3] for i in range(len(dims))]
    noisy = estimate_batch_terms(dims, _CFGS,
                                 rng=np.random.default_rng(7),
                                 routines=routines).total_s
    assert np.all(np.isfinite(noisy)) and np.all(noisy > 0)
    clean = estimate_batch_terms(dims, _CFGS, routines=routines).total_s
    assert np.all(noisy > 0.2 * clean) and np.all(noisy < 10 * clean)


def test_routine_ids_validation():
    assert routine_ids(None, 3).tolist() == [0, 0, 0]
    assert routine_ids("trsm", 2).tolist() == [2, 2]
    assert routine_ids(["gemm", "syrk"], 2).tolist() == [0, 1]
    with pytest.raises(ValueError, match="unknown routine"):
        routine_ids("cholesky", 1)
    with pytest.raises(ValueError, match="one per dim"):
        routine_ids(["gemm"], 2)


# ---------------------------------------------------------------------------
# tuner over the shared mixed-routine artifact
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_artifact_tuner_selects_consistently_per_routine(tiny_artifact):
    """select_many over a mixed-routine shape list returns exactly the
    per-routine scalar selections (routine-consistent configs)."""
    tuner = AdsalaTuner.from_artifact(tiny_artifact.dir)
    tuner._cache.clear()
    shapes = [(512, 512, 512), (64, 2048, 64), (4096, 128, 4096)]
    routines = ["gemm", "syrk", "trsm"]
    pairs = [(s, r) for s in shapes for r in routines]
    batched = tuner.select_many([s for s, _ in pairs],
                                routines=[r for _, r in pairs])
    fresh = AdsalaTuner.from_artifact(tiny_artifact.dir)
    fresh._cache.clear()
    scalar = [fresh.select(*s, routine=r) for s, r in pairs]
    assert batched == scalar
    for cfg in batched:
        assert cfg in tuner.candidates


def test_stub_tuner_batched_times_positive():
    """Cheap no-artifact check that routine columns flow through the
    feature -> predict path for every routine."""

    class _Model:
        def predict(self, X):
            return np.log(1e-6 * (X[:, 3] + 1e-3 * X[:, 0] + X[:, 20]))

    class _Pipe:
        def transform(self, X):
            return X

    t = AdsalaTuner(_Model(), _Pipe(), candidate_configs(8, tiles=(0,)))
    times = t.predicted_times_many(
        [(64, 64, 64)] * 3, routines=["gemm", "syrk", "trsm"])
    assert times.shape == (3, len(t.candidates))
    assert np.all(np.isfinite(times)) and np.all(times > 0)
