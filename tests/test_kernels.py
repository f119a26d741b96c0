"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (
    flash_attention_pallas,
    flash_attention_ref,
    grouped_matmul_pallas,
    grouped_matmul_ref,
    matmul_pallas,
    matmul_ref,
)

_RNG = np.random.default_rng(0)


def _arr(shape, dtype):
    return jnp.asarray(_RNG.standard_normal(shape), dtype=dtype)


_MATMUL_CASES = [
    # (m, k, n, bm, bk, bn)
    (64, 64, 64, 64, 64, 64),
    (128, 256, 128, 64, 128, 64),
    (100, 130, 70, 32, 64, 32),          # ragged, padded grid
    (8, 8, 8, 32, 32, 32),               # tile > dims
    (256, 64, 512, 128, 64, 128),
    (33, 257, 65, 16, 128, 16),
]


@pytest.mark.parametrize("m,k,n,bm,bk,bn", _MATMUL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_matches_oracle(m, k, n, bm, bk, bn, dtype):
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    out = matmul_pallas(a, b, bm=bm, bk=bk, bn=bn, interpret=True)
    ref = matmul_ref(a, b)
    tol = 5e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(8, 96), k=st.integers(8, 96), n=st.integers(8, 96))
def test_matmul_property_random_shapes(m, k, n):
    a, b = _arr((m, k), jnp.float32), _arr((k, n), jnp.float32)
    out = matmul_pallas(a, b, bm=32, bk=32, bn=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("m,k", [(64, 32), (100, 130), (33, 65), (8, 8)])
@pytest.mark.parametrize("lower", [True, False])
def test_syrk_matches_oracle(m, k, lower):
    from repro.kernels import syrk, syrk_ref
    a = _arr((m, k), jnp.float32)
    out = syrk(a, lower=lower, backend="pallas", interpret=True,
               tile=(32, 32, 32))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(syrk_ref(a, lower=lower)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("m,n,lower", [(64, 48, True), (100, 32, True),
                                       (64, 48, False), (33, 17, False),
                                       (16, 8, True)])
def test_trsm_matches_oracle(m, n, lower):
    from repro.kernels import trsm, trsm_ref
    ell = np.tril(_RNG.standard_normal((m, m))).astype(np.float32)
    np.fill_diagonal(ell, np.abs(np.diag(ell)) + m)   # well conditioned
    a = jnp.asarray(ell if lower else ell.T)
    b = _arr((m, n), jnp.float32)
    out = trsm(a, b, lower=lower, backend="pallas", interpret=True,
               tile=(32, 32, 32))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(trsm_ref(a, b, lower=lower)),
                               atol=1e-3, rtol=1e-3)


def test_syrk_trsm_reject_bad_shapes():
    from repro.kernels import syrk, trsm
    with pytest.raises(ValueError, match="SYRK"):
        syrk(_arr((2, 4, 4), jnp.float32), backend="xla")
    with pytest.raises(ValueError, match="TRSM"):
        trsm(_arr((4, 5), jnp.float32), _arr((4, 3), jnp.float32),
             backend="xla")
    with pytest.raises(ValueError, match="TRSM"):
        trsm(_arr((4, 4), jnp.float32), _arr((5, 3), jnp.float32),
             backend="xla")


@pytest.mark.parametrize("e,c,d,f", [(4, 64, 32, 48), (2, 100, 64, 64),
                                     (8, 16, 16, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_oracle(e, c, d, f, dtype):
    x, w = _arr((e, c, d), dtype), _arr((e, d, f), dtype)
    out = grouped_matmul_pallas(x, w, bm=32, bk=32, bn=32, interpret=True)
    ref = grouped_matmul_ref(x, w)
    tol = 5e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("seq,bq,bkv", [(128, 32, 32), (96, 32, 64),
                                        (64, 64, 64)])
@pytest.mark.parametrize("window", [None, 48])
def test_flash_attention_matches_oracle(seq, bq, bkv, window):
    q = _arr((3, seq, 64), jnp.float32)
    k = _arr((3, seq, 64), jnp.float32)
    v = _arr((3, seq, 64), jnp.float32)
    out = flash_attention_pallas(q, k, v, bq=bq, bkv=bkv, causal=True,
                                 window=window, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q = _arr((2, 64, 32), jnp.bfloat16)
    out = flash_attention_pallas(q, q, q, bq=32, bkv=32, interpret=True)
    ref = flash_attention_ref(q, q, q)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_flash_attention_rejects_bad_shapes():
    q = _arr((2, 64, 32), jnp.float32)
    k = _arr((3, 64, 32), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention_pallas(q, k, k, interpret=True)


def test_matmul_rejects_bad_shapes():
    with pytest.raises(ValueError):
        matmul_pallas(_arr((4, 8), jnp.float32), _arr((9, 4), jnp.float32),
                      interpret=True)


# ---------------------------------------------------------------------------
# dispatch layer (repro.kernels.ops)
# ---------------------------------------------------------------------------

def test_resolve_backend_validates_names():
    from repro.kernels import resolve_backend
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("auto") in ("pallas", "xla")
    for bad in ("palas", "PALLAS", "cuda", ""):
        with pytest.raises(ValueError, match="unknown backend"):
            resolve_backend(bad)


def test_ops_reject_unknown_backend():
    from repro.kernels import matmul
    a = _arr((16, 16), jnp.float32)
    with pytest.raises(ValueError, match="unknown backend"):
        matmul(a, a, backend="palas")


def _stub_tuner():
    from repro.core import AdsalaTuner, candidate_configs

    class _Model:
        def predict(self, X):
            return np.log(1e-6 * (X[:, 3] + 1e-3 * X[:, 0]))

    class _Pipe:
        def transform(self, X):
            return X

    return AdsalaTuner(_Model(), _Pipe(), candidate_configs(8, tiles=(0,)))


def test_grouped_matmul_single_batched_tuner_lookup():
    """All experts resolve through ONE select_many evaluation."""
    from repro.kernels import grouped_matmul, grouped_matmul_ref
    tuner = _stub_tuner()
    x, w = _arr((4, 32, 16), jnp.float32), _arr((4, 16, 24), jnp.float32)
    out = grouped_matmul(x, w, tuner=tuner, backend="pallas",
                         interpret=True)
    assert tuner.stats["calls"] == 4          # one per expert shape...
    assert tuner.stats["evaluations"] == 1    # ...but a single evaluation
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(grouped_matmul_ref(x, w)),
                               atol=1e-4, rtol=1e-4)


def test_grouped_matmul_group_sizes_refine_shapes():
    from repro.kernels import grouped_matmul
    tuner = _stub_tuner()
    x, w = _arr((3, 32, 16), jnp.float32), _arr((3, 16, 24), jnp.float32)
    grouped_matmul(x, w, tuner=tuner, group_sizes=[32, 8, 1],
                   backend="pallas", interpret=True)
    assert tuner.stats["calls"] == 3
    assert tuner.stats["evaluations"] == 3    # three distinct shapes
    assert ("gemm", 32, 16, 24) in tuner._cache


def test_grouped_matmul_validates_group_sizes():
    from repro.kernels import grouped_matmul
    x, w = _arr((3, 32, 16), jnp.float32), _arr((3, 16, 24), jnp.float32)
    with pytest.raises(ValueError, match="entries for"):
        grouped_matmul(x, w, group_sizes=[32, 8], backend="xla")
    with pytest.raises(ValueError, match="outside"):
        grouped_matmul(x, w, group_sizes=[32, 8, -1], backend="xla")
    with pytest.raises(ValueError, match="outside"):
        grouped_matmul(x, w, group_sizes=[32, 8, 33], backend="xla")


def test_syrk_trsm_routine_tuner_dispatch():
    """syrk/trsm consult the tuner under their own routine key — the
    same dims as a gemm call never alias its cache entry."""
    from repro.kernels import dispatch_hint, syrk, trsm
    tuner = _stub_tuner()
    a = _arr((32, 16), jnp.float32)
    syrk(a, tuner=tuner, backend="pallas", interpret=True)
    assert ("syrk", 32, 16, 32) in tuner._cache
    ell = jnp.asarray(np.tril(np.ones((32, 32), np.float32)) +
                      31 * np.eye(32, dtype=np.float32))
    trsm(ell, _arr((32, 8), jnp.float32), tuner=tuner, backend="pallas",
         interpret=True)
    assert ("trsm", 32, 32, 8) in tuner._cache
    hint = dispatch_hint(32, 16, 32, tuner, routine="syrk")
    assert hint == tuner._cache[("syrk", 32, 16, 32)][0]
    assert tuner.stats["evaluations"] == 2   # hint was a cache hit


def test_grouped_dispatch_hint_uses_select_many():
    from repro.kernels import grouped_dispatch_hint
    tuner = _stub_tuner()
    hints = grouped_dispatch_hint([(64, 32, 32)] * 5, tuner)
    assert len(hints) == 5 and len(set(hints)) == 1
    assert tuner.stats["evaluations"] == 1
    assert grouped_dispatch_hint([(64, 32, 32)], None) is None


def test_grouped_dispatch_hint_rejects_prefix_coverage():
    """A shape list covering only a prefix of the experts must raise, not
    silently leave the tail unhinted."""
    from repro.kernels import grouped_dispatch_hint
    tuner = _stub_tuner()
    with pytest.raises(ValueError, match="every expert needs a shape"):
        grouped_dispatch_hint([(64, 32, 32)] * 3, tuner, n_experts=8)
    # also guards the no-tuner path (validation before dispatch)
    with pytest.raises(ValueError, match="every expert needs a shape"):
        grouped_dispatch_hint([(64, 32, 32)] * 3, None, n_experts=8)
    assert grouped_dispatch_hint([(64, 32, 32)] * 3, None,
                                 n_experts=3) is None


def test_grouped_matmul_accepts_array_group_sizes():
    from repro.kernels import grouped_matmul, grouped_matmul_ref
    tuner = _stub_tuner()
    x, w = _arr((3, 32, 16), jnp.float32), _arr((3, 16, 24), jnp.float32)
    out = grouped_matmul(x, w, tuner=tuner,
                         group_sizes=np.array([32, 8, 1]),
                         backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(grouped_matmul_ref(x, w)),
                               atol=1e-4, rtol=1e-4)


def test_resolve_backend_env_override(monkeypatch):
    from repro.kernels.ops import resolve_backend
    monkeypatch.setenv("ADSALA_BACKEND", "xla")
    assert resolve_backend("auto") == "xla"
    monkeypatch.setenv("ADSALA_BACKEND", "pallas")
    assert resolve_backend("auto") == "pallas"
    # explicit argument wins over the environment
    assert resolve_backend("xla") == "xla"
    monkeypatch.setenv("ADSALA_BACKEND", "mosaic")
    with pytest.raises(ValueError, match="ADSALA_BACKEND"):
        resolve_backend("auto")


def test_resolve_interpret_only_on_cpu(monkeypatch):
    """Interpret mode engages only where JAX's platform is the CPU; an
    explicit choice wins everywhere."""
    from repro.kernels import ops
    assert ops.resolve_interpret() is True          # this suite: cpu
    assert ops.resolve_interpret(False) is False
    for platform, want in (("tpu", False), ("gpu", False), ("cpu", True)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda p=platform: p)
        assert ops.resolve_interpret() is want
        assert ops.resolve_interpret(True) is True
