"""Smoke run of the tuned serving path on a TPU, through its entry points.

    python chip_smoke.py             # one chip: kernels, tuner, serve
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One process, which holds the chip; nothing here starts a child that
touches JAX.  The one-chip run has four phases, in order:

1. device  — JAX must find a TPU (``JAX_PLATFORMS=tpu`` unless the caller
   set it, so a failed TPU init raises instead of falling back to the
   CPU), the kernel backend must resolve to Pallas and interpret mode
   must be off;
2. kernels — ``matmul_pallas``, ``grouped_matmul_pallas`` and
   ``flash_attention_pallas`` compiled at real widths: each HLO must hold
   a ``tpu_custom_call`` and each result must agree with its oracle in
   :mod:`repro.kernels.ref`, computed at the highest matmul precision;
3. tuner   — an ADSALA artifact installed from seed with the analytic
   ``SimulatedBackend`` into a fresh directory;
4. serve   — ``repro.launch.serve.main`` in this process: full-width
   stablelm-1.6b behind the continuous-batching queue with the tuner;
   every request must finish, and one served prompt's prefill logits
   must agree with the XLA path at the highest matmul precision.

``--chips 4`` runs only what exists across chips, each against the
same work on one device: a granite-8b train step on a 2x2 (data, model)
mesh, and a mixtral-8x22b prefill whose MoE layer runs expert-parallel
(``apply_moe_ep`` under ``shard_map``) on a 1x4 mesh.

Every phase prints what it ran, on which device, its numbers and its
error against the reference; any failure raises, and the exit code is
non-zero.  The last line of a successful run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed are smoke numbers of this run on the named device,
compilation included where it says so; they are not benchmark metrics.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or to
``<checkout>/.jax_cache`` (:mod:`repro.launch.compile_cache`).
"""

from __future__ import annotations

import os
import sys

# before JAX is imported: a TPU that fails to initialise must raise, not
# fall back to the CPU
os.environ.setdefault("JAX_PLATFORMS", "tpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import build_model, get_config  # noqa: E402
from repro.core.costmodel import DEFAULT_TILES, EXTENDED_TILES  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.kernels.grouped_matmul import grouped_matmul_pallas  # noqa: E402
from repro.kernels.matmul import matmul_pallas  # noqa: E402
from repro.kernels.ops import resolve_backend, resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: largest |kernel - oracle| / max|oracle| admitted per input dtype: the
#: chip may round fp32 matmul operands to bf16 (one MXU pass), and a
#: bf16 result carries bf16 rounding
KERNEL_BOUND = {"float32": 1e-2, "bfloat16": 2e-2}

#: largest |served - reference| / max|reference| of stablelm's last-token
#: logits: the served path runs its 24 layers of fp32 einsums at the
#: chip's default matmul precision (bf16 operands, about 2e-3 relative
#: per product, accumulating down the residual stream), the reference at
#: the highest
LOGIT_BOUND = 5e-2

#: largest |mesh - one device| / |one device| of a train-step loss: the
#: same arithmetic summed in another order
LOSS_BOUND = 1e-2

#: largest |EP - dense| / max|dense| of the mixtral prefill logits: both
#: at the chip's default matmul precision, attention by the flash kernel
#: on one device and by XLA on the mesh
MOE_BOUND = 5e-2

#: continuous-batching serve: 8 requests of 128-512 prompt tokens and
#: 4-16 output tokens (seed 1), 4 decode slots over 16-token pages
SERVE_ARGV = ["--arch", "stablelm-1.6b", "--scale", "full", "--queue",
              "--requests", "8", "--prompt-len", "512",
              "--gen-tokens", "16", "--slots", "4", "--page-size", "16"]

#: the main path's attention shapes (BH, S, Dh): stablelm's 32 heads of
#: 64 at a 2k prompt, a ragged prompt, and 128-wide heads
FLASH_SHAPES = ((32, 2048, 64), (32, 300, 64), (32, 2048, 128))


class CompileClock:
    """Backend compile seconds and persistent-cache hits in this process,
    read from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits

    def since(self, snap: tuple[float, int, int]) -> str:
        s, c, h = self.snapshot()
        return (f"compile {s - snap[0]:.3f}s over {c - snap[1]} backend "
                f"compiles ({h - snap[2]} persistent-cache hits)")


def _err(out, want) -> tuple[float, float]:
    """(max |out - want|, that over max |want|); raises on non-finite."""
    out = np.asarray(jnp.asarray(out, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    if out.shape != want.shape:
        raise AssertionError(f"shape {out.shape} != reference {want.shape}")
    if not np.isfinite(out).all():
        raise AssertionError("non-finite values in the result")
    err = float(np.max(np.abs(out - want)))
    return err, err / max(float(np.max(np.abs(want))), 1e-30)


def _check(label: str, out, want, bound: float) -> None:
    err, rel = _err(out, want)
    verdict = "ok" if rel <= bound else "FAIL"
    print(f"  {label}: max|err|={err:.6g} rel={rel:.6g} "
          f"bound={bound:g} {verdict}", flush=True)
    if rel > bound:
        raise AssertionError(f"{label}: rel err {rel:.6g} > {bound:g}")


def check_device(chips: int) -> dict:
    """Phase 1: the platform, the device count and the kernel path."""
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {d.platform}")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips asked for, {len(devs)} found")
    backend, interpret = resolve_backend(), resolve_interpret()
    print(f"[device] kernel backend={backend} interpret={interpret}",
          flush=True)
    if backend != "pallas" or interpret:
        raise SystemExit("the kernels would not run compiled on the TPU")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _oracle(fn, *args, **kw):
    """A reference result at the highest matmul precision (the kernels
    themselves are traced outside this context)."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def _compiled_run(label: str, fn, *args, custom_call: bool = True):
    """Compile ``fn`` for ``args``, check the HLO holds the Mosaic
    kernel, run it once and return the result."""
    compiled = jax.jit(fn).lower(*args).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if custom_call and not has_kernel:
        raise AssertionError(f"{label}: no tpu_custom_call in the HLO")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    print(f"  {label}: tpu_custom_call={'yes' if has_kernel else 'no'} "
          f"run {time.perf_counter() - t0:.6f}s", flush=True)
    return out


def kernel_phase(*, mm_shape=(2048, 2048, 5632), tiles=EXTENDED_TILES,
                 gmm_shape=(8, 128, 6144, 16384),
                 flash_shapes=FLASH_SHAPES,
                 dtypes=(jnp.float32, jnp.bfloat16),
                 interpret: bool = False) -> None:
    """Phase 2: the three Pallas kernels, compiled, against their
    oracles.  ``interpret=True`` is for a rehearsal off the chip."""
    kind = jax.devices()[0].device_kind
    key = jax.random.PRNGKey(0)
    m, k, n = mm_shape
    for dt in dtypes:
        a = jax.random.normal(key, (m, k), dt)
        b = jax.random.normal(jax.random.fold_in(key, 1), (k, n), dt)
        want = _oracle(ref.matmul_ref, a, b, out_dtype=jnp.float32)
        print(f"[kernels] matmul_pallas {m}x{k}x{n} {dt.__name__} on "
              f"{kind}, per tile", flush=True)
        for bm, bk, bn in tiles:
            out = _compiled_run(
                f"tile=({bm},{bk},{bn})",
                lambda a, b, bm=bm, bk=bk, bn=bn: matmul_pallas(
                    a, b, bm=bm, bk=bk, bn=bn, interpret=interpret),
                a, b, custom_call=not interpret)
            _check(f"tile=({bm},{bk},{bn}) vs matmul_ref", out, want,
                   KERNEL_BOUND[dt.__name__])

    e, c, d, f = gmm_shape
    bm, bk, bn = DEFAULT_TILES[3]
    x = jax.random.normal(key, (e, c, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 2), (e, d, f),
                          jnp.bfloat16)
    print(f"[kernels] grouped_matmul_pallas {e} experts x ({c}x{d} @ "
          f"{d}x{f}) bfloat16 tile=({bm},{bk},{bn}) on {kind}", flush=True)
    out = _compiled_run(
        "grouped", lambda x, w: grouped_matmul_pallas(
            x, w, bm=bm, bk=bk, bn=bn, interpret=interpret),
        x, w, custom_call=not interpret)
    _check("grouped vs grouped_matmul_ref", out,
           _oracle(ref.grouped_matmul_ref, x, w, out_dtype=jnp.float32),
           KERNEL_BOUND["bfloat16"])
    del x, w, out

    for bh, s, dh in flash_shapes:
        for dt in dtypes:
            q, kk, v = (jax.random.normal(jax.random.fold_in(key, i),
                                          (bh, s, dh), dt)
                        for i in range(3))
            want = _oracle(ref.flash_attention_ref,
                           *(t.astype(jnp.float32) for t in (q, kk, v)),
                           causal=True)
            print(f"[kernels] flash_attention_pallas causal BH={bh} S={s} "
                  f"Dh={dh} {dt.__name__} on {kind}", flush=True)
            for grid in ("dense", "tri"):
                out = _compiled_run(
                    f"grid={grid}",
                    lambda q, k, v, grid=grid: flash_attention_pallas(
                        q, k, v, causal=True, grid=grid,
                        interpret=interpret),
                    q, kk, v, custom_call=not interpret)
                _check(f"grid={grid} vs flash_attention_ref", out, want,
                       KERNEL_BOUND[dt.__name__])


def tuner_phase(root: str) -> str:
    """Phase 3: install a small mixed-routine artifact from seed with the
    analytic backend into ``root``/artifact; returns its directory."""
    from repro.core import InstallConfig, SimulatedBackend, install

    out = os.path.join(root, "artifact")
    cfg = InstallConfig(
        n_samples=48, repeats=2, tile_ids=(0, 3),
        models=("linear_regression", "decision_tree", "xgboost"),
        routines=("gemm", "syrk", "trsm", "attn"),
        grid_budget="small", cv_splits=3, seed=0)
    t0 = time.perf_counter()
    report = install(SimulatedBackend(seed=0), cfg, artifact_dir=out)
    print(f"[tuner] installed {cfg.routines} from seed 0 with "
          f"SimulatedBackend into a fresh directory in "
          f"{time.perf_counter() - t0:.3f}s; model={report.selected}",
          flush=True)
    return out


def serve_phase(argv: list[str]) -> dict:
    """Phase 4a: ``repro.launch.serve.main`` in this process."""
    from repro.launch.serve import main as serve_main

    kind = jax.devices()[0].device_kind
    print(f"[serve] repro.launch.serve {' '.join(argv)} on {kind}",
          flush=True)
    res = serve_main(argv)
    n_req = int(argv[argv.index("--requests") + 1])
    done = len(res["finished"])
    print(f"[serve] {done}/{n_req} requests finished, alloc.check() "
          f"passed; {res['tokens']} tokens in {res['wall_s']:.6f}s = "
          f"{res['tok_s']:.6f} tok/s (smoke number on {kind}, compiles "
          f"included)", flush=True)
    if done != n_req:
        raise AssertionError(f"{done} of {n_req} requests finished")
    return res


def prefill_reference_phase(arch: str, scale: str, prompt: tuple[int, ...],
                            artifact: str, page_size: int) -> None:
    """Phase 4b: one served prompt through ``model.prefill`` on the tuned
    path and on the XLA path at the highest precision."""
    from repro.configs import get_smoke_config
    from repro.core import AdsalaTuner
    from repro.serve.kv_cache import pages_for
    from repro.train.step import make_ctx

    cfg = (get_config if scale == "full" else get_smoke_config)(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))   # serve's seed
    tuner = AdsalaTuner.from_artifact(artifact)
    ctx = make_ctx(None, "prefill", remat=False, tuner=tuner,
                   cache_len=pages_for(len(prompt), page_size) * page_size)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    served = jax.jit(lambda p, t: model.prefill(p, t, ctx)[0])(params, toks)
    # the backend is resolved while tracing: a second function object, so
    # no trace of the tuned path is reused
    old = os.environ.get("ADSALA_BACKEND")
    os.environ["ADSALA_BACKEND"] = "xla"
    try:
        want = _oracle(jax.jit(lambda p, t: model.prefill(p, t, ctx)[0]),
                       params, toks)
    finally:
        if old is None:
            del os.environ["ADSALA_BACKEND"]
        else:
            os.environ["ADSALA_BACKEND"] = old
    print(f"[serve] prefill of a served {len(prompt)}-token prompt: tuned "
          f"{resolve_backend()} path vs XLA at highest precision, on "
          f"{jax.devices()[0].device_kind}", flush=True)
    _check("last-token logits", served, want, LOGIT_BOUND)


def _spread(label: str, tree, compiled) -> None:
    """Print and check that ``tree`` is spread over the mesh's devices:
    per-device bytes from the shards and the compiled program's
    per-device argument bytes.  All on one device would hold the whole
    tree; split over a model axis of 2 or more, a device holds about
    half of it or less (only norms and counters are replicated)."""
    per_dev: dict = {}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    arg = compiled.memory_analysis().argument_size_in_bytes
    print(f"  {label}: {total} bytes in all; per device "
          f"{dict(sorted(per_dev.items()))}; memory_analysis argument "
          f"bytes per device {arg}", flush=True)
    if len(per_dev) < 2 or max(per_dev.values()) > 0.6 * total \
            or arg > 0.6 * total:
        raise AssertionError(f"{label}: not spread across the devices")


def sharded_train_phase(cfg, *, mesh_shape=(2, 2), batch: int = 4,
                        seq: int = 256, steps: int = 3) -> None:
    """Four chips: ``build_train_step`` on a (data, model) mesh against
    the same steps on one device."""
    from repro.dist.sharding import named_shardings
    from repro.launch.mesh import make_mesh
    from repro.models.config import ShapeSpec
    from repro.train.optim import AdamWConfig
    from repro.train.step import build_train_step, init_train_state

    model = build_model(cfg)
    shape = ShapeSpec("smoke", seq, batch, "train")
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps)
    tok = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                             cfg.vocab)
    data = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}   # next token

    def init():
        return init_train_state(model, cfg, opt, jax.random.PRNGKey(0))

    def losses(step, state, data) -> list[float]:
        out = []
        for _ in range(steps):
            state, metrics = step(state, data)
            out.append(float(metrics["loss"]))
        return out

    kind = jax.devices()[0].device_kind
    print(f"[train] {cfg.name} ({cfg.n_layers} layers, d={cfg.d_model}, "
          f"ff={cfg.d_ff}, vocab={cfg.vocab}) batch {batch}x{seq}, "
          f"{steps} AdamW steps on {kind}", flush=True)
    one_step, _, _ = build_train_step(model, cfg, shape, None, opt)
    t0 = time.perf_counter()
    want = losses(jax.jit(one_step, donate_argnums=(0,)), jax.jit(init)(),
                  data)
    print(f"  one device: losses {want} in "
          f"{time.perf_counter() - t0:.3f}s (compile included)", flush=True)

    mesh = make_mesh(mesh_shape, ("data", "model"))
    fn, s_specs, b_specs = build_train_step(model, cfg, shape, mesh, opt)
    s_sh = named_shardings(mesh, s_specs)
    b_sh = named_shardings(mesh, b_specs)
    state = jax.jit(init, out_shardings=s_sh)()
    data = jax.device_put(data, b_sh)
    step = jax.jit(fn, in_shardings=(s_sh, b_sh),
                   out_shardings=(s_sh, None), donate_argnums=(0,))
    _spread(f"train state on mesh {dict(mesh.shape)}", state,
            step.lower(state, data).compile())
    t0 = time.perf_counter()
    got = losses(step, state, data)
    print(f"  mesh {dict(mesh.shape)}: losses {got} in "
          f"{time.perf_counter() - t0:.3f}s", flush=True)
    for i, (g, w) in enumerate(zip(got, want)):
        rel = abs(g - w) / abs(w)
        ok = np.isfinite(g) and rel <= LOSS_BOUND
        print(f"  step {i}: |mesh - one device| / |one device| = "
              f"{rel:.6g} bound={LOSS_BOUND:g} {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            raise AssertionError(f"train step {i}: loss {g} vs {w}")


def moe_ep_phase(cfg, *, mesh_shape=(1, 4), batch: int = 1,
                 seq: int = 8, dtype=jnp.float32) -> None:
    """Four chips: a MoE prefill with ``apply_moe_ep`` under
    ``shard_map`` against the dense one-hot path on one device.

    ``batch * seq`` is held to 8 tokens: then neither path can drop a
    token (the dense path's capacity over all tokens and the
    expert-parallel path's per-shard capacity are both at least 8, the
    most any expert can receive), so the two must agree."""
    from repro.dist.sharding import named_shardings
    from repro.launch.mesh import make_mesh
    from repro.models.config import ShapeSpec
    from repro.serve.step import build_prefill

    # fp32 parameters of the 1-layer cut fit one v5e (10.8 GB by the
    # compiler's memory analysis), so the reference runs on one device
    model = build_model(cfg)
    shape = ShapeSpec("smoke", seq, batch, "prefill")
    tok = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                        (batch, seq), 0, cfg.vocab)}

    def init():
        return model.init(jax.random.PRNGKey(0), dtype)

    kind = jax.devices()[0].device_kind
    print(f"[moe] {cfg.name} ({cfg.n_layers} layer, d={cfg.d_model}, "
          f"{cfg.n_experts} experts of ff={cfg.d_ff_expert}) "
          f"{jnp.dtype(dtype).name} prefill of {batch}x{seq} tokens on "
          f"{kind}", flush=True)
    one, _, _ = build_prefill(model, cfg, shape, None)
    want = jax.jit(lambda p, b: one(p, b)[0])(jax.jit(init)(), tok)
    want = np.asarray(want.astype(jnp.float32))

    mesh = make_mesh(mesh_shape, ("data", "model"))
    fn, p_specs, b_specs = build_prefill(model, cfg, shape, mesh)
    p_sh = named_shardings(mesh, p_specs)
    params = jax.jit(init, out_shardings=p_sh)()
    tok = jax.device_put(tok, named_shardings(mesh, b_specs))
    run = jax.jit(lambda p, b: fn(p, b)[0], in_shardings=(p_sh, None))
    compiled = run.lower(params, tok).compile()
    hlo = compiled.as_text()
    print(f"  mesh {dict(mesh.shape)}: all-to-all ops "
          f"{hlo.count(' all-to-all')}, tpu_custom_call "
          f"{'yes' if 'tpu_custom_call' in hlo else 'no'}", flush=True)
    _spread("params", params, compiled)
    got = compiled(params, tok)
    _check("EP logits vs dense one-hot on one device", got, want,
           MOE_BOUND)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded paths, on four chips")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    device = check_device(args.chips)
    print(f"[device] compile cache {cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()

    def phase(name: str, fn, *a, **kw):
        snap, start = clock.snapshot(), time.perf_counter()
        out = fn(*a, **kw)
        print(f"[{name}] phase {time.perf_counter() - start:.3f}s wall; "
              f"{clock.since(snap)}", flush=True)
        return out

    if args.chips == 4:
        phase("train", sharded_train_phase,
              dataclasses.replace(get_config("granite-8b"), n_layers=2))
        phase("moe", moe_ep_phase,
              dataclasses.replace(get_config("mixtral-8x22b"), n_layers=1))
    else:
        phase("kernels", kernel_phase)
        with tempfile.TemporaryDirectory(prefix="adsala_smoke_") as root:
            artifact = phase("tuner", tuner_phase, root)
            res = phase("serve", serve_phase,
                        SERVE_ARGV + ["--artifact", artifact])
            prompt = max((f.prompt for f in res["finished"].values()),
                         key=len)
            del res
            gc.collect()     # serve's parameters leave the device
            phase("reference", prefill_reference_phase, "stablelm-1.6b",
                  "full", prompt, artifact, page_size=16)
    print(f"[done] {time.perf_counter() - t0:.3f}s wall; "
          f"{clock.since((0.0, 0, 0))} in all", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
