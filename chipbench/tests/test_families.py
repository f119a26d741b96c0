"""The family seam.

The dense family gives back, bit for bit, what the harness computed
before it had families: operation counts and the parameter layout at
the published widths, the drawn weights and the reference's logits (and
the fp8 control's) at smoke size. The golden values below were taken
from the harness as it stood before the split into families.

A family defined only in new files (a module, a configuration naming
it, a mix and limits) is resolved by ``spec.load_cell`` and serves a
smoke cell end to end; an unknown or incomplete family fails at load.
"""

import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
import run
import spec
from conftest import smoke_cell
from repro.configs import build_model

SEED = 2**33 + 7

DENSE = {
    "stablelm-1.6b.chat": {
        "decode": {1: 2877489152, 384: 2952790016, 4096: 3682598912},
        "prefill": {1: 2877489152.0, 64: 158660034560.0,
                    1024: 2629031690240.0, 4032: 11542860922880.0},
        "tree": "PyTreeDef({'embed': *, 'ln_f': {'bias': *, 'scale': *}, "
                "'prefix': [], 'scan': [{'ln1': {'bias': *, 'scale': *}, "
                "'ln2': {'bias': *, 'scale': *}, 'mixer': {'wk': *, "
                "'wo': *, 'wq': *, 'wv': *}, 'mlp': {'wg': *, 'wi': *, "
                "'wo': *}}], 'suffix': [], 'unembed': *})",
        "shapes": [(100352, 2048), (2048,), (2048,), (24, 2048), (24, 2048),
                   (24, 2048), (24, 2048), (24, 2048, 2048),
                   (24, 2048, 2048), (24, 2048, 2048), (24, 2048, 2048),
                   (24, 2048, 5632), (24, 2048, 5632), (24, 5632, 2048),
                   (2048, 100352)],
        "smoke": {"d": 64, "ff": 128, "heads": 4, "kv_heads": 4,
                  "head_dim": 16, "layers": 2, "vocab": 256, "rotary": 0.25,
                  "rope_theta": 10000.0, "norm": "layernorm",
                  "norm_eps": 1e-05, "tied": False},
        "params": "df86ce5b9d2840094bb6fa6dcdce5374"
                  "f752b7399c01c3875429d1822df9519a",
        "logits": "d05029b19ad55dbaff1aea0d2ad5318c"
                  "66da1e611fa2f3fc85397fbd2afbbfc3",
        "fp8": "e6a85c808d1b7295411569ac09e2bc07"
               "aac1c9a1b676f862b318919d8b78709e",
    },
    "granite-8b.code": {
        "decode": {1: 8254685184, 384: 8367636480, 4096: 9462349824},
        "prefill": {1: 8254685184.0, 64: 503527243776.0,
                    1024: 8195351248896.0, 4032: 34056396865536.0},
        "tree": "PyTreeDef({'embed': *, 'ln_f': {'scale': *}, "
                "'prefix': [], 'scan': [{'ln1': {'scale': *}, "
                "'ln2': {'scale': *}, 'mixer': {'wk': *, 'wo': *, "
                "'wq': *, 'wv': *}, 'mlp': {'wg': *, 'wi': *, 'wo': *}}], "
                "'suffix': [], 'unembed': *})",
        "shapes": [(49152, 4096), (4096,), (18, 4096), (18, 4096),
                   (18, 4096, 1024), (18, 4096, 4096), (18, 4096, 4096),
                   (18, 4096, 1024), (18, 4096, 14336), (18, 4096, 14336),
                   (18, 14336, 4096), (4096, 49152)],
        "smoke": {"d": 64, "ff": 128, "heads": 4, "kv_heads": 1,
                  "head_dim": 16, "layers": 2, "vocab": 256, "rotary": 1.0,
                  "rope_theta": 10000.0, "norm": "rmsnorm",
                  "norm_eps": 1e-06, "tied": False},
        "params": "e689e2002164028d6c7dbbb459bcab28"
                  "c19936a7d53e49253ccebdae7b852943",
        "logits": "a2499ccf0eef98f55a9732da89bf9306"
                  "604052337b8075498e4d7e01f6447825",
        "fp8": "339c0145af3ab53b3f903b038bbf4c20"
               "143804dfb26876a03eea6d9a48f8c2eb",
    },
}

WORKLOADS = list(DENSE)
by_config = pytest.mark.parametrize("workload", WORKLOADS,
                                    ids=lambda w: w.rsplit(".", 1)[0])


@by_config
def test_dense_counts_are_the_parents(workload):
    cell = spec.load_cell(workload)
    want = DENSE[workload]
    assert cell.family.__name__ == "chipbench_family_dense"
    for ctx, f in want["decode"].items():
        assert cell.family.decode_flops(cell.k, ctx) == f
    for n, f in want["prefill"].items():
        assert cell.family.prefill_flops(cell.k, n) == f


@by_config
def test_dense_parameter_layout_is_the_parents(workload):
    cell = spec.load_cell(workload)
    fam = cell.family
    model = build_model(fam.program_config(cell.config))
    tree = jax.eval_shape(lambda: fam.program_params(
        model, cell.k, 0, jnp.bfloat16))
    assert str(jax.tree.structure(tree)) == DENSE[workload]["tree"]
    leaves = jax.tree.leaves(tree)
    assert [a.shape for a in leaves] == DENSE[workload]["shapes"]
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}


@by_config
def test_dense_smoke_weights_are_the_parents(workload):
    cell = smoke_cell(workload)
    assert cell.k == DENSE[workload]["smoke"]
    fam = cell.family
    model = build_model(fam.program_config(cell.config))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(fam.program_params(model, cell.k, SEED,
                                                   jnp.bfloat16)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == DENSE[workload]["params"]


@pytest.mark.parametrize("fp8", [False, True], ids=["reference", "fp8"])
@by_config
def test_dense_reference_logits_are_the_parents(workload, fp8):
    cell = smoke_cell(workload)
    ref = cell.family.Reference(cell.k, SEED)
    tokens = np.random.default_rng(11).integers(0, 256, 40).astype(np.int32)
    got = np.asarray(ref.logits(tokens, fp8=fp8), np.float32)
    assert got.shape == (256, 256)
    assert hashlib.sha256(got.tobytes()).hexdigest() == \
        DENSE[workload]["fp8" if fp8 else "logits"]


# ---------------------------------------------------------- a new family

ECHO = '''"""The dense family under another name."""
import families

_dense = families.load("dense")
globals().update({n: getattr(_dense, n) for n in families.REQUIRED})
'''

PARTIAL = '''"""A family that defines only its sizes."""
def dims(config):
    return {"vocab": int(config["vocab_size"])}
'''


def _checkout(root, family, source=None) -> str:
    """A checkout at ``root`` with one more cell, ``echo.code``:
    granite-8b's configuration naming ``family``, the code mix,
    granite-8b.code's limits, and ``families/<family>.py`` holding
    ``source``. Only new files: nothing of this checkout is touched."""
    here = root / "chipbench"
    for d in ("configs", "traffic", "limits", "families"):
        (here / d).mkdir(parents=True)
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "echo", "source": "granite-8b's", "reduced": [],
        "file": "chipbench/configs/echo.json", "why": "a new family"})
    bench["workloads"].append({"name": "echo.code", "config": "echo",
                               "traffic": "code", "chips": 1,
                               "why": "a new family"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite-8b.code" in m.get("workloads", []):
            m["workloads"].append("echo.code")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = json.loads((spec.BENCH_DIR / "configs" /
                      "granite-8b.json").read_text())
    cfg.update(name="echo", family=family)
    if family is None:
        del cfg["family"]
    (here / "configs" / "echo.json").write_text(json.dumps(cfg))
    shutil.copy(spec.BENCH_DIR / "traffic" / "code.json", here / "traffic")
    shutil.copy(spec.BENCH_DIR / "limits" / "granite-8b.code.json",
                here / "limits" / "echo.code.json")
    if source is not None:
        (here / "families" / f"{family}.py").write_text(source)
    return "echo.code"


def _files() -> dict:
    """Every file of this checkout's benchmark, by its digest."""
    out = {}
    for p in sorted([spec.ROOT / "BENCHMARK.json",
                     *spec.BENCH_DIR.rglob("*")]):
        if p.is_file() and not {".cache", "__pycache__"} & set(p.parts):
            out[str(p)] = hashlib.sha256(p.read_bytes()).hexdigest()
    return out


def test_a_new_family_is_new_files_only(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "_peaks", lambda dev: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    before = _files()
    workload = _checkout(tmp_path, "echo", ECHO)
    cell = spec.load_cell(workload, tmp_path)
    assert cell.family.__file__ == str(tmp_path / "chipbench" / "families"
                                       / "echo.py")
    assert cell.k == families.load("dense").dims(cell.config)
    small = smoke_cell(workload, tmp_path)
    res = run.run_cell(small, 2**33 + 3, 2.0, False,
                       session=run.Session(small, tuner=False), t_start=0.0)
    assert res["compared_tokens"] > 0
    assert res["correct"] is True, res["checks"]
    assert _files() == before


@pytest.mark.parametrize("family,source,says", [
    ("nosuch", None, r"unknown family 'nosuch': the families are \[\]"),
    (None, None, r"unknown family None"),
    ("partial", PARTIAL, r"does not define \['program_config', "
                         r"'program_params', 'Reference', 'decode_flops', "
                         r"'prefill_flops', 'smoke'\]"),
], ids=["unknown", "unnamed", "incomplete"])
def test_a_family_unknown_or_incomplete_fails_at_load(tmp_path, family,
                                                      source, says):
    workload = _checkout(tmp_path, family, source)
    with pytest.raises(ValueError, match=says):
        spec.load_cell(workload, tmp_path)


def test_an_unknown_family_names_the_families_there():
    with pytest.raises(ValueError, match=r"the families are \['dense'\]"):
        families.load("deepseek")
