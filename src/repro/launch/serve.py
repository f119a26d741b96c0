"""Serving launcher: batched prefill + decode loop with the ADSALA tuner.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --scale smoke --requests 4 --gen-tokens 16 \
        --artifact results/adsala_artifact

Demonstrates the runtime workflow of the paper (Fig 3): the tuner is
loaded once at boot, consulted per GEMM *shape* (memoised — repeated
decode steps hit the cache), and its chosen worker configurations are
reported alongside the generation stats.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, build_model, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import Ctx, make_ctx

#: total-variation distance between the serving routine mix and the
#: installed workload profile above which serve warns (0 = identical)
DRIFT_WARN = 0.25


def main(argv: list[str] | None = None) -> dict | None:
    """Parse ``argv`` (default ``sys.argv[1:]``) and serve.

    ``--queue`` returns the continuous-batching run's summary (see
    :func:`_serve_queue`); the fixed-batch loop returns None.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b", choices=ARCH_IDS)
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--queue", action="store_true",
                    help="trace-driven continuous batching: serve a "
                         "ragged request queue (prompt lengths up to "
                         "--prompt-len, outputs up to --gen-tokens) "
                         "through the paged-KV scheduler instead of "
                         "one fixed batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (batch width) in --queue mode")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size (token slots) in --queue mode")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="total KV pages in the shared pool (default: "
                         "2x worst case for --slots sequences)")
    ap.add_argument("--artifact", default=None,
                    help="ADSALA artifact dir (tuner enabled when set)")
    ap.add_argument("--registry", default=None,
                    help="per-architecture artifact registry root: "
                         "fingerprint this host and serve from its own "
                         "cell, falling back to the nearest populated "
                         "neighbour (mutually exclusive with "
                         "--artifact); with --reinstall the loop "
                         "targets this machine's cell")
    ap.add_argument("--search-width", type=int, default=None,
                    help="beam width for dispatch-time config search "
                         "over the artifact's persisted space (default: "
                         "fixed-candidate argmin, the paper's policy)")
    ap.add_argument("--profile-out", default=None,
                    help="write the recorded dispatch mix as a "
                         "WorkloadProfile JSON (feed it back into the "
                         "installer via repro.launch.profile)")
    ap.add_argument("--profile-by", default="flops",
                    choices=["flops", "events"],
                    help="dispatch-volume weighting of --profile-out; "
                         "keep the default to merge with dry-run "
                         "profiles (repro.launch.profile uses flops "
                         "weighting by default, and mixed weightings "
                         "refuse to merge)")
    ap.add_argument("--reinstall", action="store_true",
                    help="close the serving loop: watch live dispatch "
                         "drift vs the installed workload profile and "
                         "re-install + hot-swap the artifact in the "
                         "background when it crosses the threshold "
                         "(requires --artifact)")
    ap.add_argument("--reinstall-threshold", type=float, default=0.25,
                    help="drift (total variation, 0..1) that triggers "
                         "a background re-install")
    ap.add_argument("--reinstall-budget", type=int, default=2000,
                    help="timing budget (cells) for each background "
                         "re-install; keeps the online install cheap")
    ap.add_argument("--reinstall-cooldown", type=float, default=300.0,
                    help="minimum seconds between re-installs")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_config if args.scale == "full"
           else get_smoke_config)(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    from repro.kernels.recorder import DispatchRecorder

    # separate recorders per traffic class: prefill and decode have very
    # different shape profiles, and the re-install manager merges them
    # volume-weighted so the install budget follows serving volume
    recs = {"prefill": DispatchRecorder(), "decode": DispatchRecorder()}

    fingerprint = None
    if args.registry:
        if args.artifact:
            raise SystemExit("--registry and --artifact are mutually "
                             "exclusive: the registry resolves the "
                             "artifact by this machine's fingerprint")
        from repro.core.registry import (ArtifactRegistry,
                                         resolve_serving_artifact)
        resolved = resolve_serving_artifact(args.registry)
        fingerprint = resolved.local
        if resolved.path is None:
            raise SystemExit(
                f"registry {args.registry} has no servable artifact in "
                f"any cell — run an install first "
                "(repro.launch.profile --registry ...)")
        if not resolved.exact and args.reinstall:
            # the re-install loop must own a LOCAL cell (never
            # overwrite the neighbour's artifact with this machine's
            # corrected timings): seed ours by adopting the neighbour
            reg = ArtifactRegistry(args.registry)
            args.artifact = reg.adopt(fingerprint, resolved.path)
            print(f"[serve] registry: cold cell {fingerprint.key()} "
                  f"seeded from nearest neighbour "
                  f"{resolved.cell.key()} (adopt; re-installs stay "
                  "local)")
        else:
            args.artifact = resolved.path
            cell = ("own cell" if resolved.exact
                    else f"nearest cell {resolved.cell.key()}")
            print(f"[serve] registry: serving {cell} for "
                  f"{fingerprint.key()}")

    tuner = None
    manager = None
    if args.artifact and os.path.isdir(args.artifact):
        mode = (f"beam search width {args.search_width}"
                if args.search_width else "fixed-candidate argmin")
        if args.reinstall:
            from repro.core.installer import InstallConfig
            from repro.serve import ReinstallConfig, ReinstallManager
            # backend=None on purpose: the manager rebuilds the same
            # kind of backend that installed the artifact (its
            # "backend" provenance block) — a measured artifact
            # re-installs measured, legacy ones fall back to the
            # simulator
            manager = ReinstallManager(
                args.artifact, recs,
                fingerprint=fingerprint,
                cfg=ReinstallConfig(
                    threshold=args.reinstall_threshold,
                    cooldown_s=args.reinstall_cooldown,
                    min_events=8,
                    install=InstallConfig(
                        n_samples=160, repeats=2,
                        models=("lightgbm",),
                        timing_budget=args.reinstall_budget)),
                search_width=args.search_width)
            tuner = manager
            print(f"[serve] ADSALA tuner loaded from {args.artifact} "
                  f"({mode}); online re-install armed at drift > "
                  f"{args.reinstall_threshold}")
        else:
            from repro.core import AdsalaTuner
            tuner = AdsalaTuner.from_artifact(
                args.artifact, search_width=args.search_width,
                local_fingerprint=fingerprint)
            print(f"[serve] ADSALA tuner loaded from {args.artifact} "
                  f"({mode})")
    elif args.reinstall:
        raise SystemExit("--reinstall requires --artifact (or "
                         "--registry) pointing at an installed ADSALA "
                         "artifact")

    if args.queue:
        return _serve_queue(args, cfg, model, params, tuner, manager, recs)

    cache_len = args.prompt_len + args.gen_tokens
    pctx = make_ctx(None, "prefill", cache_len=cache_len, remat=False,
                    tuner=tuner)
    dctx = make_ctx(None, "decode", cache_len=cache_len, tuner=tuner)

    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        rng, (args.requests, args.prompt_len), 0, cfg.vocab)
    batch_extra = {}
    if cfg.family == "audio":
        batch_extra["audio_emb"] = jax.random.normal(
            rng, (args.requests, cfg.encoder_len, cfg.d_model))

    prefill = jax.jit(lambda p, t: model.prefill(
        p, ({"tokens": t, **batch_extra} if cfg.family == "audio" else t),
        pctx))
    decode = jax.jit(lambda p, tok, c, pos: model.decode_step(
        p, tok, c, pos, dctx))

    t0 = time.perf_counter()
    # the recorders observe the trace-time dispatches of both steps:
    # which routine every contraction was tagged as, per call site
    with recs["prefill"]:
        logits, cache = prefill(params, prompts)
        logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    if tuner is not None:
        # the serving GEMM shapes the tuner is consulted for
        d = cfg.d_model
        shapes = [(args.requests * args.prompt_len, d, d),  # qkv/o proj
                  (args.requests, d, cfg.vocab)]            # decode logits
        for (m, k, n) in shapes:
            c = tuner.select(m, k, n)
            print(f"[serve] tuner GEMM {m}x{k}x{n} -> chips={c.n_chips} "
                  f"partition={c.partition} tile={c.tile}")

    toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    generated = [toks]
    t0 = time.perf_counter()
    for i in range(args.gen_tokens - 1):
        with recs["decode"]:        # decode dispatches trace on step 0
            logits, cache = decode(params, toks,
                                   cache, jnp.int32(args.prompt_len + i))
        toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        generated.append(toks)
        if manager is not None and manager.check():
            print(f"[serve] drift {manager.last_drift:.3f} crossed "
                  f"{args.reinstall_threshold} at decode step {i}: "
                  "background re-install launched (serving continues)")
    jax.block_until_ready(generated[-1])
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(generated, axis=1)
    tps = args.requests * (args.gen_tokens - 1) / max(t_decode, 1e-9)
    print(f"[serve] {cfg.name}: {args.requests} requests, "
          f"prefill {args.prompt_len} toks in {t_prefill*1e3:.1f}ms, "
          f"decoded {args.gen_tokens} toks at {tps:.1f} tok/s")
    print(f"[serve] sample continuation ids: {out[0, :8].tolist()}")
    _report_tail(args, cfg, recs, tuner, manager)


def _report_tail(args, cfg, recs, tuner, manager) -> None:
    """Shared post-run reporting: routine mix, tuner/re-install stats,
    optional --profile-out — identical for fixed-batch and --queue."""
    from repro.kernels.recorder import DispatchRecorder

    # combined view across traffic classes for reporting / --profile-out
    rec = DispatchRecorder()
    for r in recs.values():
        rec.events.extend(r.events)
    mix = rec.routine_mix(by="events")
    if mix:
        pretty = " ".join(f"{r}={f:.2f}" for r, f in mix.items())
        print(f"[serve] dispatch routine mix (by events): {pretty} "
              f"over {len(rec.events)} traced events")
    if manager is not None:
        if manager.installing:
            print("[serve] waiting for the background re-install...")
        manager.wait()
        if manager.last_error is not None:
            print(f"[serve] re-install failed (old artifact still "
                  f"serving): {manager.last_error!r}")
        drift = manager.drift()
        print(f"[serve] tuner stats: {tuner.stats}")
        print(f"[serve] re-install: fires={manager.fires} "
              f"swaps={manager.swaps} post-swap drift="
              f"{'n/a' if drift is None else format(drift, '.3f')}")
    elif tuner is not None:
        print(f"[serve] tuner stats: {tuner.stats}")
        # compare the live mix against the profile the install grid was
        # weighted by (same weighting the profile was built with)
        if tuner.workload is not None and rec.events:
            drift = tuner.workload_drift(
                rec.routine_mix(by=tuner.workload.by))
            print(f"[serve] workload drift vs installed profile: "
                  f"{drift:.3f} (total variation)")
            if drift > DRIFT_WARN:
                print(f"[serve] WARNING: serving mix drifted "
                      f"{drift:.2f} > {DRIFT_WARN} from the installed "
                      "workload profile — the install budget was spent "
                      "on a different routine mix; re-profile and "
                      "re-install (repro.launch.profile)")
    if args.profile_out:
        from repro.core.workload import WorkloadProfile
        prof = WorkloadProfile.from_recorder(
            rec, by=args.profile_by,
            source={"kind": "serve", "arch": cfg.name,
                    "queue": bool(args.queue),
                    "requests": args.requests,
                    "prompt_len": args.prompt_len,
                    "gen_tokens": args.gen_tokens})
        prof.save(args.profile_out)
        print(f"[serve] workload profile written to {args.profile_out}")


def _latency_ms(finished: dict) -> dict:
    """p50 / p90 (ms) and sample count of queue wait, time to first
    token and the gap between consecutive tokens, from the scheduler's
    own timestamps (:class:`~repro.serve.scheduler.FinishedSeq`)."""
    import numpy as np

    fs = list(finished.values())
    samples = {
        "queue_wait_ms": [f.admitted_at - f.submitted_at for f in fs],
        "ttft_ms": [f.token_times[0] - f.submitted_at for f in fs],
        "itl_ms": [b - a for f in fs
                   for a, b in zip(f.token_times, f.token_times[1:])],
    }
    out = {}
    for name, xs in samples.items():
        ms = 1e3 * np.asarray(xs, np.float64)
        out[name] = {"p50": float(np.percentile(ms, 50)) if xs else None,
                     "p90": float(np.percentile(ms, 90)) if xs else None,
                     "n": len(xs)}
    return out


def _serve_queue(args, cfg, model, params, tuner, manager, recs) -> dict:
    """Trace-driven continuous batching: ragged requests through the
    paged-KV scheduler, re-install drift checks riding the step hook.

    Returns ``{"finished": {rid: FinishedSeq}, "tokens", "queue_wait_ms",
    "ttft_ms", "itl_ms"}``, each latency ``{"p50", "p90", "n"}`` from
    the scheduler's timestamps (:func:`_latency_ms`). Every request is
    queued at the start and each prompt length compiles its prefill on
    first use, so queue wait and time to first token include compiles.
    """
    import numpy as np

    from repro.serve.kv_cache import pages_for
    from repro.serve.scheduler import ContinuousBatchingScheduler

    max_seq = args.prompt_len + args.gen_tokens
    worst = pages_for(max_seq, args.page_size)
    n_pages = (args.kv_pages if args.kv_pages is not None
               else 2 * args.slots * worst)
    sched = ContinuousBatchingScheduler(
        model, cfg, params, slots=args.slots, n_pages=n_pages,
        page_size=args.page_size, max_seq_len=max_seq, tuner=tuner,
        recorders=recs)

    rng = np.random.default_rng(1)
    for _ in range(args.requests):
        length = int(rng.integers(max(2, args.prompt_len // 4),
                                  args.prompt_len + 1))
        new = int(rng.integers(max(1, args.gen_tokens // 4),
                               args.gen_tokens + 1))
        sched.submit(rng.integers(0, cfg.vocab, length).tolist(), new)

    def on_step(s):
        if manager is not None and manager.check():
            print(f"[serve] drift {manager.last_drift:.3f} crossed the "
                  f"threshold at decode step {s.steps}: background "
                  "re-install launched (serving continues)")

    finished = sched.run_until_drained(on_step=on_step)

    toks = sum(len(f.tokens) for f in finished.values())
    lat = _latency_ms(finished)
    print(f"[serve] {cfg.name}: {len(finished)} requests via "
          f"continuous batching ({args.slots} slots, {n_pages} pages x "
          f"{args.page_size} tokens), {toks} tokens generated, goodput "
          f"{sched.goodput():.3f} tok/slot-step over {sched.steps} steps")
    for name, what in (("queue_wait_ms", "queue wait"),
                       ("ttft_ms", "time to first token"),
                       ("itl_ms", "gap between tokens")):
        q = lat[name]
        if q["n"]:
            print(f"[serve] {what}: p50 {q['p50']:.3f} ms, p90 "
                  f"{q['p90']:.3f} ms over {q['n']}")
    sample = min(finished)
    print(f"[serve] sample continuation ids: "
          f"{list(finished[sample].tokens)[:8]}")
    sched.alloc.check()
    _report_tail(args, cfg, recs, tuner, manager)
    return {"finished": finished, "tokens": toks, **lat}


if __name__ == "__main__":
    main()
