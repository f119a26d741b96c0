"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --scale smoke --steps 50 --ckpt-dir /tmp/ckpt

Scales:
  smoke — reduced config, CPU-sized, no mesh (CI / laptop)
  full  — the assigned config on the production mesh (TPU pod)

Wraps the step loop in the fault-tolerant driver (checkpoint/restart,
preemption handling, straggler detection) and the prefetching data
pipeline.  When an ADSALA artifact is supplied the tuner is loaded and
its worker-config choices are logged for the serve path.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, build_model, get_config, get_smoke_config
from repro.data.pipeline import Prefetcher, SyntheticLM, make_global_batch
from repro.ft.driver import DriverConfig, TrainDriver
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, ShapeSpec
from repro.train.optim import AdamWConfig
from repro.train.step import build_train_step, init_train_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b", choices=ARCH_IDS)
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/adsala_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.scale == "full":
        cfg = get_config(args.arch)
        mesh = make_production_mesh()
        shape = SHAPES["train_4k"]
    else:
        cfg = get_smoke_config(args.arch)
        mesh = None
        shape = ShapeSpec("custom", args.seq, args.batch, "train")

    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps,
                          compress=args.compress_grads)
    step_fn, s_specs, b_specs = build_train_step(
        model, cfg, shape, mesh, opt_cfg)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    state = init_train_state(model, cfg, opt_cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"[train] {cfg.name} scale={args.scale} params={n_params:,}")

    data_src = SyntheticLM(
        cfg.vocab, shape.seq_len, shape.global_batch,
        audio_dim=cfg.d_model if cfg.family == "audio" else None,
        audio_len=cfg.encoder_len)
    data = ({k: jnp.asarray(v) for k, v in b.items()}
            for b in Prefetcher(iter(data_src), depth=2))

    driver = TrainDriver(
        DriverConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     max_steps=args.steps),
        jit_step, state, data, mesh=mesh, specs=s_specs)
    if args.resume:
        resumed = driver.maybe_resume()
        print(f"[train] resumed from step {resumed}")

    t0 = time.perf_counter()
    summary = driver.run()
    dt = time.perf_counter() - t0
    print(f"[train] done: step={summary['step']} "
          f"loss={summary['last_metrics'].get('loss', float('nan')):.4f} "
          f"wall={dt:.1f}s stragglers={len(summary['stragglers'])}")


if __name__ == "__main__":
    main()
