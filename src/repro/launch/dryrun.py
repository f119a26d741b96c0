"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes and record memory/cost/collective analyses.

MUST be executed as its own process (``python -m repro.launch.dryrun``):
the environment lines below pin the CPU platform (a CPU-only program
must not claim a TPU it finds) and create 512 placeholder host devices,
and must run before any other jax import in the process.

Per cell this emits results/dryrun/<arch>_<shape>_<mesh>.json with:
  memory_analysis  — bytes per device (arguments / temp / output / peak)
  cost_analysis    — per-device HLO FLOPs + bytes accessed
  collectives      — per-op-kind byte totals parsed from post-SPMD HLO
  model_flops      — 6·N·D (dense) / 6·N_active·D (MoE) for §Roofline
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, build_model, get_config
from repro.dist.sharding import named_shardings
from repro.kernels.recorder import DispatchRecorder
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, ShapeSpec
from repro.serve.step import (
    build_decode,
    build_prefill,
    decode_inputs_sds,
    prefill_batch_sds,
)
from repro.train.optim import AdamWConfig
from repro.train.step import abstract_state, build_train_step, train_batch_sds

_DTYPE = jnp.bfloat16

#: long_500k eligibility (DESIGN.md §Arch-applicability): sub-quadratic
#: state only — recurrent or window-bounded caches.
LONG_OK = {"mixtral-8x22b", "recurrentgemma-2b", "xlstm-125m"}


def cell_is_runnable(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in LONG_OK:
        return False, ("full-attention arch: 524288-token dense KV cache "
                       "is quadratic-cost; skipped per DESIGN.md")
    return True, ""


_SHAPE_RE = re.compile(
    r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
    r"\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
                "u16": 2, "u8": 1, "pred": 1}
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt.split("e")[0][:4], 2)
    return total


def parse_collectives(hlo: str) -> dict:
    """Sum output-shape bytes of every collective op in post-SPMD HLO.

    Shapes in the partitioned module are PER-DEVICE.  ``-start`` /
    ``-done`` pairs are counted once (on the start op).  Ops are
    bucketed by scope: "entry" (executed once) vs "loop" (inside a
    non-entry computation — scan/while bodies, executed trip-count
    times; the roofline post-processing multiplies by the recorded
    layer-loop trip count, XLA cost analysis counts them once).
    """
    out = {k: {"count": 0, "bytes": 0, "loop_count": 0, "loop_bytes": 0}
           for k in _COLL_KINDS}
    in_entry = False
    for line in hlo.splitlines():
        stripped = line.strip()
        if stripped.startswith("ENTRY"):
            in_entry = True
        elif stripped.startswith("}") and not line.startswith(" "):
            in_entry = False
        elif re.match(r"^%?\S+ \(", stripped) and stripped.endswith("{") \
                and not line.startswith(" "):
            in_entry = False
        if "=" not in stripped:
            continue
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([a-z0-9-]+)", stripped)
        if not m:
            continue
        op = m.group(2)
        base = op.removesuffix("-start")
        if op.endswith("-done"):
            continue
        if base in _COLL_KINDS:
            nbytes = _shape_bytes(m.group(1))
            if in_entry:
                out[base]["count"] += 1
                out[base]["bytes"] += nbytes
            else:
                out[base]["loop_count"] += 1
                out[base]["loop_bytes"] += nbytes
    return out


def build_cell(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]

    if shape.kind == "train":
        fn, s_specs, b_specs = build_train_step(
            model, cfg, shape, mesh, AdamWConfig())
        state_sds = abstract_state(model, cfg, AdamWConfig(), _DTYPE)
        batch_sds = train_batch_sds(cfg, shape, _DTYPE)
        in_shardings = (named_shardings(mesh, s_specs),
                        named_shardings(mesh, b_specs))
        out_shardings = (named_shardings(mesh, s_specs), None)
        args = (state_sds, batch_sds)
    elif shape.kind == "prefill":
        fn, p_specs, b_specs = build_prefill(model, cfg, shape, mesh)
        from repro.models.params import abstract_params
        params_sds = abstract_params(model.defs, _DTYPE)
        batch_sds = prefill_batch_sds(cfg, shape, _DTYPE)
        in_shardings = (named_shardings(mesh, p_specs),
                        named_shardings(mesh, b_specs))
        out_shardings = None
        args = (params_sds, batch_sds)
    else:  # decode
        fn, p_specs, io_specs = build_decode(model, cfg, shape, mesh)
        from repro.models.params import abstract_params
        params_sds = abstract_params(model.defs, _DTYPE)
        token_sds, cache_sds_, pos_sds = decode_inputs_sds(
            model, cfg, shape, _DTYPE)
        t_spec, c_specs, pos_spec = io_specs
        in_shardings = (named_shardings(mesh, p_specs),
                        named_shardings(mesh, t_spec),
                        named_shardings(mesh, c_specs),
                        named_shardings(mesh, pos_spec))
        out_shardings = (None, named_shardings(mesh, c_specs))
        args = (params_sds, token_sds, cache_sds_, pos_sds)
    return cfg, model, fn, args, in_shardings, out_shardings


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "n_devices": 512 if multi_pod else 256}
    ok, why = cell_is_runnable(arch, shape_name)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg, model, fn, args, in_sh, out_sh = build_cell(arch, shape_name,
                                                     mesh)

    t0 = time.time()
    donate = ((0,) if os.environ.get("ADSALA_DONATE") == "1"
              and shape_name.startswith("train") else ())
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    # the routine-aware call sites report their dispatches at trace
    # time, so wrapping .lower() yields the cell's per-call-site
    # routine mix — how much of this arch's dispatch volume is
    # SYRK/TRSM-eligible — with zero extra compile work
    with DispatchRecorder() as rec:
        lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per exec
        cost = cost[0] if cost else {}
    colls = parse_collectives(compiled.as_text())
    shape = SHAPES[shape_name]
    n_tok = (shape.tokens if shape.kind != "decode"
             else shape.global_batch)
    flops_factor = 6 if shape.kind == "train" else 2
    record.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            # CPU-backed jax builds expose no peak stat; args+temp is
            # the live-set upper bound the roofline needs
            "peak_bytes": getattr(
                mem, "peak_memory_in_bytes",
                mem.argument_size_in_bytes + mem.temp_size_in_bytes),
        },
        "cost": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_per_device": cost.get("bytes accessed", 0.0),
        },
        "collectives": colls,
        # trace-time dispatch observability (events are per call site
        # per trace: scanned layer stacks count once per unit layer —
        # a routine *mix*, not an absolute count)
        "dispatch": {
            "events": len(rec.events),
            "routine_mix": rec.routine_mix(),
            "routine_mix_events": rec.routine_mix(by="events"),
            "summary": rec.summary(),
            # aggregated (routine, m, k, n) rows: what
            # repro.launch.profile folds into a WorkloadProfile to
            # weight the install grid by this cell's workload
            "shapes": rec.shape_table(),
        },
        "model": {
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens": n_tok,
            # 6ND train / 2ND inference per token
            "model_flops": flops_factor * cfg.active_param_count() * n_tok,
        },
        # trip counts for the xla-counts-loop-bodies-once correction
        "loops": {
            "layer_repeats": getattr(model, "repeats", 0),
            "prefix_layers": len(getattr(model, "prefix", [])),
            "suffix_layers": len(getattr(model, "suffix", [])),
            "unit_len": len(getattr(model, "unit", [])),
            "n_layers": cfg.n_layers,
        },
    })
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell for --mesh")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None \
        else [args.shape]
    meshes = [False, True] if args.mesh == "both" \
        else [args.mesh == "multi"]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                path = os.path.join(
                    args.out, f"{arch}_{shape}_{mesh_name}.json")
                if os.path.exists(path):
                    print(f"[dryrun] cached {path}")
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_name} ...",
                      flush=True)
                try:
                    rec = run_cell(arch, shape, multi, args.out)
                except Exception as e:  # noqa: BLE001 - report and continue
                    rec = {"arch": arch, "shape": shape,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    gb = rec["memory"]["argument_bytes"] / 2**30
                    extra = (f" compile={rec['compile_s']}s "
                             f"args={gb:.2f}GiB/dev")
                print(f"[dryrun]   -> {status}{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
