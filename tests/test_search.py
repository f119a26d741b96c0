"""The compositional search harness: ConfigSpace + SearchGraph + beam.

Three contracts anchor the refactor:

* the default space's exhaustive enumeration is bit-for-bit the
  historical ``candidate_configs`` grid (every artifact pin survives);
* a full-width, full-depth beam returns exactly the exhaustive argmin
  for every routine (ties included — first-occurrence order);
* a narrow beam over the ~11x enlarged space finds the optimum while
  pricing a small fraction of it (the smoke benchmark's claim).
"""

import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AdsalaTuner,
    Axis,
    ConfigSpace,
    Gate,
    GemmConfig,
    SearchGraph,
    beam_search,
    candidate_configs,
    exhaustive_best,
    gather_data,
    install,
)
from repro.core.costmodel import (
    DEFAULT_TILES,
    EXTENDED_TILES,
    TRSM_SEQ_CHIPS,
    chip_doublings,
)
from repro.core.installer import InstallConfig
from repro.core.timing import SimulatedBackend

# ---------------------------------------------------------------------------
# ConfigSpace: enumeration parity, gates, serialisation, sampling
# ---------------------------------------------------------------------------


def _legacy_candidate_loop(max_chips, tiles, partitions):
    """The pre-refactor candidate_configs triple loop, re-rolled."""
    out = []
    for c in chip_doublings(max_chips):
        for p in partitions:
            if p == "2D" and c < 4:
                continue
            for t in tiles:
                out.append(GemmConfig(c, p, t))
    return out


@pytest.mark.parametrize("max_chips,tiles,parts", [
    (512, tuple(range(len(DEFAULT_TILES))), ("M", "N", "K", "2D")),
    (64, (0, 3), ("M", "N", "K", "2D")),
    (8, (0, 1, 3, 5), ("M", "2D")),
    (6, (3,), ("M", "N", "K", "2D")),
    (1, (0,), ("M", "N", "K")),
])
def test_default_space_enumeration_is_legacy_grid(max_chips, tiles, parts):
    space = ConfigSpace.default(max_chips, tiles=tiles, partitions=parts)
    assert space.enumerate() == _legacy_candidate_loop(
        max_chips, tiles, parts)
    assert space.size() == len(space.enumerate())


def test_candidate_configs_routes_through_the_space():
    """The public enumeration API is now a thin view of ConfigSpace."""
    assert candidate_configs(512) == ConfigSpace.default(512).enumerate()
    assert candidate_configs(64, tiles=(0, 3)) == \
        ConfigSpace.default(64, tiles=(0, 3)).enumerate()


def test_min_chips_gate_defers_then_fires():
    space = ConfigSpace.default(512)
    # partition assigned before chips: gate defers (admits)
    assert space.check({"partition": "2D"})
    # chips joins below the submesh minimum: gate fires
    assert not space.check({"partition": "2D", "n_chips": 2})
    assert space.check({"partition": "2D", "n_chips": 4})


def test_min_local_gate_is_dims_aware():
    space = ConfigSpace.enlarged(512, min_local=8)
    tiny = (9, 17, 33)
    # sharding M over 512 chips leaves <8 rows per chip
    assert not space.check({"partition": "M", "n_chips": 512}, dims=tiny)
    assert space.check({"partition": "M", "n_chips": 1}, dims=tiny)
    # without dims the gate is a no-op
    assert space.check({"partition": "M", "n_chips": 512})
    # enumeration honours it: no huge-chip shardings for tiny dims
    for cfg in space.enumerate(dims=tiny):
        assert space.contains(cfg, dims=tiny)
    assert space.size(dims=tiny) < space.size()


def test_space_serialisation_round_trip():
    for space in (ConfigSpace.default(64, tiles=(0, 3)),
                  ConfigSpace.enlarged(512)):
        d = json.loads(json.dumps(space.to_dict()))   # through JSON
        back = ConfigSpace.from_dict(d)
        assert back == space
        assert back.enumerate() == space.enumerate()
    with pytest.raises(ValueError, match="version"):
        ConfigSpace.from_dict({"version": 99, "axes": []})


def test_space_requires_core_axes():
    with pytest.raises(ValueError, match="n_chips"):
        ConfigSpace((Axis("partition", ("M",)), Axis("tile_id", (0,))))
    with pytest.raises(ValueError, match="unknown axis"):
        ConfigSpace((Axis("n_chips", (1,)), Axis("partition", ("M",)),
                     Axis("tile_id", (0,)), Axis("warp_size", (32,))))


def test_enlarged_space_is_10x_and_contains_default():
    default = ConfigSpace.default(512)
    enlarged = ConfigSpace.enlarged(512)
    assert enlarged.size() >= 10 * default.size()
    for cfg in default.enumerate():
        assert enlarged.contains(cfg)
    # knob values beyond the fixed default become members
    assert enlarged.contains(GemmConfig(8, "M", 3, trsm_seq_chips=8))
    assert not default.contains(GemmConfig(8, "M", 3, trsm_seq_chips=8))


def test_sample_is_deterministic_and_in_space():
    space = ConfigSpace.enlarged(512)
    a = space.sample(25, seed=7)
    b = space.sample(25, seed=7)
    assert a == b
    assert len(set(a)) == len(a) == 25
    assert all(space.contains(c) for c in a)
    assert space.sample(25, seed=8) != a


def test_complete_uses_canonical_defaults():
    space = ConfigSpace.enlarged(512)
    cfg = space.complete({})
    assert (cfg.n_chips, cfg.partition, cfg.tile_id,
            cfg.trsm_seq_chips) == (512, "2D", 3, TRSM_SEQ_CHIPS)
    # default inadmissible under the partial -> first admissible value
    cfg = space.complete({"n_chips": 2})
    assert cfg.partition == "M"   # 2D needs >= 4 chips
    with pytest.raises(ValueError, match="no admissible"):
        ConfigSpace.default(512).complete({"n_chips": 2,
                                           "partition": "2D"})


def test_search_graph_refines_in_order():
    space = ConfigSpace.default(64, tiles=(0, 3))
    g = SearchGraph(space, order=("partition", "n_chips", "tile_id"))
    s = g.initial()
    assert not g.is_complete(s)
    assert list(g.actions(s)) == ["M", "N", "K", "2D"]
    s = g.apply(s, "2D")
    # chips below the 2D submesh minimum are not offered
    assert all(c >= 4 for c in g.actions(s))
    s = g.apply(s, 4)
    s = g.apply(s, 3)
    assert g.is_complete(s)
    assert g.config(s) == GemmConfig(4, "2D", 3)


# ---------------------------------------------------------------------------
# beam search: exactness at full width, quality at narrow width
# ---------------------------------------------------------------------------

_ROUTINE_CASES = [None, "gemm", "syrk", "trsm",
                  ["gemm", "syrk", "trsm", "gemm"]]


@settings(max_examples=10, deadline=None)
@given(m=st.integers(8, 65536), k=st.integers(8, 65536),
       n=st.integers(8, 65536),
       routine=st.sampled_from(("gemm", "syrk", "trsm")))
def test_full_width_beam_is_exhaustive_argmin(m, k, n, routine):
    """Satellite property: at full width/depth the beam equals the
    exhaustive enumeration's argmin bit for bit, per routine."""
    space = ConfigSpace.default(512)
    dims = np.array([[m, k, n]])
    beam = beam_search(dims, space, width=space.size(), top_k=3,
                       routines=routine)
    exact = exhaustive_best(dims, space, top_k=3, routines=routine)
    assert beam.configs == exact.configs
    assert beam.costs == exact.costs


@pytest.mark.parametrize("routines", _ROUTINE_CASES)
def test_full_width_beam_matches_exhaustive_mixed(routines):
    rng = np.random.default_rng(11)
    dims = rng.integers(8, 32768, size=(4, 3)).astype(np.int64)
    space = ConfigSpace.default(512)
    beam = beam_search(dims, space, width=space.size(),
                       routines=routines)
    exact = exhaustive_best(dims, space, routines=routines)
    assert beam.configs == exact.configs


def test_full_width_beam_exact_on_enlarged_space():
    rng = np.random.default_rng(5)
    dims = rng.integers(8, 32768, size=(3, 3)).astype(np.int64)
    space = ConfigSpace.enlarged(512)
    routines = ["gemm", "syrk", "trsm"]
    beam = beam_search(dims, space, width=space.size(),
                       routines=routines)
    exact = exhaustive_best(dims, space, routines=routines)
    assert beam.configs == exact.configs


def test_narrow_beam_quality_and_cost_on_enlarged_space():
    """The smoke claim in miniature: width 8 finds the exhaustive
    optimum on the ~11x space while pricing <= 25% of it."""
    rng = np.random.default_rng(2)
    dims = rng.integers(8, 65536, size=(8, 3)).astype(np.int64)
    routines = [("gemm", "syrk", "trsm")[i % 3] for i in range(len(dims))]
    space = ConfigSpace.enlarged(512)
    beam = beam_search(dims, space, width=8, routines=routines)
    exact = exhaustive_best(dims, space, routines=routines)
    regret = [b[0] / e[0] for b, e in zip(beam.costs, exact.costs)]
    assert max(regret) <= 1.01
    assert beam.priced_fraction <= 0.25
    assert beam.n_priced < exact.n_priced


def test_beam_handles_gated_out_branches():
    """Tiny dims make whole partition branches uncompletable under
    min_local gates; the beam must drop them, not crash."""
    space = ConfigSpace.enlarged(512, min_local=8)
    res = beam_search(np.array([[9, 17, 33]]), space, width=4)
    assert len(res.configs[0]) == 1
    assert space.contains(res.configs[0][0], dims=(9, 17, 33))


def test_beam_validates_width():
    space = ConfigSpace.default(8, tiles=(0,))
    with pytest.raises(ValueError, match="width"):
        beam_search(np.array([[64, 64, 64]]), space, width=0)


# ---------------------------------------------------------------------------
# installer integration: budgeted gathering + artifact space round-trip
# ---------------------------------------------------------------------------

def _budget_cfg(**kw):
    base = dict(n_samples=16, repeats=2, tile_ids=(0, 3),
                models=("linear_regression",),
                routines=("gemm", "syrk", "trsm"),
                timing_budget=16 * 10, seed=0)
    base.update(kw)
    return InstallConfig(**base)


def test_budgeted_gather_times_only_selected_cells():
    cfg = _budget_cfg()
    data = gather_data(SimulatedBackend(seed=0), cfg)
    assert data.mask is not None and data.mask.dtype == bool
    D, C = data.times.shape
    assert data.mask.shape == (D, C)
    quota = max(2, cfg.timing_budget // cfg.n_samples)
    per_dim = data.mask.sum(axis=1)
    assert np.all(per_dim >= 2) and np.all(per_dim <= quota)
    assert int(data.mask.sum()) <= cfg.timing_budget
    # untimed cells are +inf, timed cells finite
    assert np.all(np.isinf(data.times[~data.mask]))
    assert np.all(np.isfinite(data.times[data.mask]))
    # the baseline default config is timed for every dim (speedup denom)
    j_def = data.cfgs.index(cfg.default_config)
    assert np.all(data.mask[:, j_def])
    # training rows only come from timed cells
    X, y = data.to_rows()
    assert np.all(np.isfinite(y)) and len(y) == int(data.mask.sum())


def test_budgeted_gather_round_trips_through_npz(tmp_path):
    data = gather_data(SimulatedBackend(seed=0), _budget_cfg())
    p = str(tmp_path / "grid.npz")
    data.save(p)
    from repro.core.installer import GatheredData
    back = GatheredData.load(p)
    np.testing.assert_array_equal(back.mask, data.mask)
    np.testing.assert_array_equal(back.times, data.times)
    assert back.cfgs == data.cfgs
    assert back.space == data.space


def test_budgeted_install_artifact_serves(tmp_path):
    """A sparse-grid install trains, persists its space, and serves."""
    cfg = _budget_cfg()
    backend = SimulatedBackend(seed=0)
    data = gather_data(backend, cfg)
    report = install(backend, cfg, data=data,
                     artifact_dir=str(tmp_path))
    assert report.artifact_dir == str(tmp_path)
    conf = json.load(open(tmp_path / "config.json"))
    assert conf["install"]["timing_budget"] == cfg.timing_budget
    assert ConfigSpace.from_dict(conf["space"]) == cfg.resolved_space()
    tuner = AdsalaTuner.from_artifact(str(tmp_path))
    assert isinstance(tuner.select(1024, 512, 256, "trsm"), GemmConfig)


def test_artifact_space_block_round_trip(tiny_artifact):
    """The persisted "space" block reconstructs the exact install space
    and the tuner adopts it."""
    conf = json.load(open(tiny_artifact.dir + "/config.json"))
    space = ConfigSpace.from_dict(conf["space"])
    assert space == tiny_artifact.cfg.resolved_space()
    tuner = AdsalaTuner.from_artifact(tiny_artifact.dir)
    assert tuner.space == space
    # every candidate is a member; enumeration matches the artifact list
    assert space.enumerate() == tuner.candidates


def test_legacy_artifact_without_space_block(tiny_artifact, tmp_path):
    """Pre-search artifacts carry no "space" block; the tuner
    reconstructs the default space the candidate list implies."""
    import shutil
    legacy = tmp_path / "legacy"
    shutil.copytree(tiny_artifact.dir, legacy)
    conf = json.load(open(legacy / "config.json"))
    del conf["space"]
    json.dump(conf, open(legacy / "config.json", "w"))
    tuner = AdsalaTuner.from_artifact(str(legacy))
    assert tuner.space.enumerate() == tuner.candidates


def test_warm_start_accepts_beam_found_configs(tiny_artifact, tmp_path):
    """v3 warm blocks carry explicit configs; anything inside the
    persisted space loads even if it is not the dense argmin — that is
    what lets budgeted/beam installs warm-start the tuner."""
    import shutil
    edited = tmp_path / "beamish"
    shutil.copytree(tiny_artifact.dir, edited)
    conf = json.load(open(edited / "config.json"))
    space = ConfigSpace.from_dict(conf["space"])
    # replace the first entry with a different in-space config
    current = conf["warm_start"]["configs"][0]
    other = next(c for c in space.enumerate()
                 if {"n_chips": c.n_chips, "partition": c.partition,
                     "tile_id": c.tile_id} != current)
    conf["warm_start"]["configs"][0] = {
        "n_chips": other.n_chips, "partition": other.partition,
        "tile_id": other.tile_id}
    json.dump(conf, open(edited / "config.json", "w"))
    tuner = AdsalaTuner.from_artifact(str(edited))   # no warning
    ws = conf["warm_start"]
    assert len(tuner._cache) == len(ws["dims"])
    key = (ws["routines"][0], *ws["dims"][0])
    assert tuner._cache[key][0] == other


# ---------------------------------------------------------------------------
# tuner dispatch-time search
# ---------------------------------------------------------------------------

class _StubModel:
    """log-time grows with chips and m: argmin is fewest-chips."""

    def predict(self, X):
        return np.log(1e-6 * (X[:, 3] + 1e-3 * X[:, 0]))


class _IdentityPipe:
    def transform(self, X):
        return X


def _stub_tuner(**kw):
    return AdsalaTuner(_StubModel(), _IdentityPipe(),
                       candidate_configs(64, tiles=(0, 3)), **kw)


def test_select_search_matches_fixed_argmin_for_default_space():
    """Over the same space the beam (full width) picks exactly what the
    fixed-candidate argmin picks — the search path is a refactor, not a
    behaviour change, until the space grows."""
    t_fixed = _stub_tuner()
    t_beam = _stub_tuner()
    shapes = [(64, 64, 64), (512, 512, 512), (64, 2048, 64)]
    fixed = t_fixed.select_many(shapes)
    beamed = t_beam.select_many(shapes,
                                search=t_beam.space.size())
    assert beamed == fixed
    assert set(t_beam.stats) == {"calls", "cache_hits", "evaluations"}
    assert t_beam.stats["evaluations"] == len(shapes)


def test_select_search_memoises_and_search_width_default():
    t = _stub_tuner(search_width=4)
    cfg = t.select(256, 128, 256, "syrk")          # beam path (width 4)
    assert t.space.contains(cfg)
    again = t.select(256, 128, 256, "syrk")        # cache hit, no beam
    assert again == cfg
    assert t.stats == {"calls": 2, "cache_hits": 1, "evaluations": 1}
    # search=False forces the fixed path even with a default width
    t2 = _stub_tuner(search_width=4)
    assert t2.select(256, 128, 256, search=False) in t2.candidates


def test_select_search_over_wider_space_reaches_new_configs():
    """Give the tuner a space wider than its candidate list: the beam
    can select configs the fixed argmin cannot express."""
    space = ConfigSpace.default(64)                # all 6 tiles
    t = _stub_tuner(space=space)                   # candidates: tiles 0,3
    cfg = t.select(64, 64, 64, search=space.size())
    fixed = _stub_tuner().select(64, 64, 64)
    # stub model is tile-blind, so ties resolve to tile 0 either way;
    # the searched config must at minimum be a space member and as good
    t_chk = _stub_tuner(space=space)
    times = t_chk.predicted_times_many([(64, 64, 64)],
                                       candidates=[cfg, fixed])
    assert space.contains(cfg)
    assert times[0, 0] <= times[0, 1]


def test_select_with_times_after_search():
    t = _stub_tuner(search_width=8)
    cfg, times = t.select_with_times(128, 64, 128)
    assert len(times) == len(t.candidates)
    assert t.candidates[int(np.argmin(times))].n_chips == cfg.n_chips
