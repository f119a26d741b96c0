"""Property tests for the closed serving loop's math and debouncing.

* drift is a metric-shaped score: in [0, 1], symmetric, 0 on self;
* WorkloadProfile.merge volume-weighting is associative up to floating
  tolerance (merging per-traffic-class profiles in any grouping gives
  the same install weighting);
* the DriftTrigger hysteresis invariant: no two fires within the
  cooldown, regardless of the drift trajectory, and a second fire
  requires re-arming below threshold - hysteresis.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.costmodel import ROUTINES
from repro.core.workload import WorkloadProfile
from repro.kernels.recorder import DispatchEvent, DispatchRecorder
from repro.serve import DriftTrigger

pytestmark = pytest.mark.timeout(120)


def _rand_profile(seed: int, by: str = "flops") -> WorkloadProfile:
    rng = np.random.default_rng(seed)
    rec = DispatchRecorder()
    for _ in range(int(rng.integers(1, 50))):
        m, k, n = (int(x) for x in 2 ** rng.integers(3, 14, 3))
        rec.events.append(DispatchEvent(
            routine=ROUTINES[int(rng.integers(len(ROUTINES)))],
            m=m, k=k, n=n, count=int(rng.integers(1, 5)),
            site="prop"))
    return WorkloadProfile.from_recorder(rec, by=by)


# ---------------------------------------------------------------------------
# drift: bounded, symmetric, zero on self
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(sa=st.integers(0, 10**6), sb=st.integers(0, 10**6))
def test_drift_in_unit_interval_and_symmetric(sa, sb):
    a, b = _rand_profile(sa), _rand_profile(sb)
    d = a.drift(b)
    assert 0.0 <= d <= 1.0
    assert d == pytest.approx(b.drift(a), abs=1e-12)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(0, 10**6))
def test_drift_zero_on_self(s):
    a = _rand_profile(s)
    assert a.drift(a) == pytest.approx(0.0, abs=1e-12)
    # the routine-mix (mapping) entry point agrees on the self case
    assert a.drift(a.routine_weights) == pytest.approx(0.0, abs=1e-12)


@settings(max_examples=15, deadline=None)
@given(sa=st.integers(0, 10**6), sb=st.integers(0, 10**6))
def test_profile_drift_dominates_routine_only_drift(sa, sb):
    """The profile-vs-profile drift (max of routine and shape-cell TV)
    can only sharpen, never soften, the routine-mix warning the serve
    loop printed before the closed loop existed."""
    a, b = _rand_profile(sa), _rand_profile(sb)
    assert a.drift(b) >= a.drift(b.routine_weights) - 1e-12


# ---------------------------------------------------------------------------
# merge: volume-weighting associative up to tolerance
# ---------------------------------------------------------------------------

def _assert_profiles_close(p: WorkloadProfile, q: WorkloadProfile):
    assert p.total == pytest.approx(q.total, rel=1e-9)
    assert set(p.routine_weights) == set(q.routine_weights)
    for r, w in p.routine_weights.items():
        assert w == pytest.approx(q.routine_weights[r], abs=1e-9)
    assert set(p.cells) == set(q.cells)
    for c, w in p.cells.items():
        assert w == pytest.approx(q.cells[c], abs=1e-9)


@settings(max_examples=15, deadline=None)
@given(sa=st.integers(0, 10**6), sb=st.integers(0, 10**6),
       sc=st.integers(0, 10**6))
def test_merge_volume_weighting_associative(sa, sb, sc):
    a, b, c = (_rand_profile(s) for s in (sa, sb, sc))
    flat = WorkloadProfile.merge([a, b, c])
    left = WorkloadProfile.merge([WorkloadProfile.merge([a, b]), c])
    right = WorkloadProfile.merge([a, WorkloadProfile.merge([b, c])])
    _assert_profiles_close(flat, left)
    _assert_profiles_close(flat, right)


@settings(max_examples=15, deadline=None)
@given(sa=st.integers(0, 10**6), sb=st.integers(0, 10**6))
def test_merge_weights_follow_recorded_volume(sa, sb):
    """Default merge weights are each profile's recorded total — the
    per-traffic-class semantics the ReinstallManager relies on."""
    a, b = _rand_profile(sa), _rand_profile(sb)
    merged = WorkloadProfile.merge([a, b])
    explicit = WorkloadProfile.merge([a, b],
                                     weights=[a.total, b.total])
    _assert_profiles_close(merged, explicit)
    assert merged.total == pytest.approx(a.total + b.total, rel=1e-9)


# ---------------------------------------------------------------------------
# trigger: hysteresis + cooldown invariants over arbitrary trajectories
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(threshold=st.floats(0.05, 0.9),
       hyst_frac=st.floats(0.0, 1.0),
       cooldown=st.floats(0.0, 50.0),
       drifts=st.lists(st.floats(0.0, 1.0), min_size=1, max_size=60),
       dt=st.floats(0.1, 5.0))
def test_trigger_cooldown_and_hysteresis_invariants(
        threshold, hyst_frac, cooldown, drifts, dt):
    trig = DriftTrigger(threshold=threshold,
                        hysteresis=hyst_frac * threshold,
                        cooldown_s=cooldown)
    fires = []
    for i, d in enumerate(drifts):
        now = i * dt
        if trig.observe(d, now):
            fires.append((now, i))
            # a fire only ever happens above threshold
            assert d > threshold
    # no two fires within the cooldown, regardless of trajectory
    for (t0, _), (t1, _) in zip(fires, fires[1:]):
        assert t1 - t0 >= cooldown
    # between consecutive fires the drift must have re-armed the
    # trigger by dipping to threshold - hysteresis or below
    rearm = max(threshold - trig.hysteresis, 0.0)
    for (_, i0), (_, i1) in zip(fires, fires[1:]):
        assert any(d <= rearm for d in drifts[i0 + 1:i1])


def test_trigger_rejects_bad_params():
    with pytest.raises(ValueError):
        DriftTrigger(threshold=0.0)
    with pytest.raises(ValueError):
        DriftTrigger(threshold=0.2, hysteresis=0.3)
    with pytest.raises(ValueError):
        DriftTrigger(cooldown_s=-1.0)


def test_trigger_oscillation_fires_once():
    """Hovering just around the threshold (the thrash scenario
    hysteresis exists for) fires exactly once."""
    trig = DriftTrigger(threshold=0.25, hysteresis=0.05, cooldown_s=0.0)
    seq = [0.26, 0.24, 0.26, 0.24, 0.26]    # never dips to 0.20
    fired = sum(trig.observe(d, float(i)) for i, d in enumerate(seq))
    assert fired == 1
