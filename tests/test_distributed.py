"""Distributed behaviour on simulated host devices.

XLA locks the device count at first jax init, so these tests run their
bodies in subprocesses with XLA_FLAGS set — the same pattern the
dry-run uses.

On small hosts (<= 2 CPU cores, e.g. the CI container) the 8-device
shard_map compiles blow the 420 s subprocess budget, so the spawned
world shrinks to a 2-device (1, 2) mesh and the per-case work scales
down with it.  Set ``ADSALA_DIST_FULL=1`` (or run on a bigger host) for
the full-size 8-device meshes.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# per-test wall budget: the subprocess itself is capped at 420 s below,
# so 480 s only triggers when the parent wedges outside subprocess.run
# (enforced by pytest-timeout, or its signal fallback in conftest)
pytestmark = [pytest.mark.slow, pytest.mark.timeout(480)]

_FULL = ((os.cpu_count() or 1) > 2
         or os.environ.get("ADSALA_DIST_FULL") == "1")
_DEVICES = 8 if _FULL else 2
_MESH_A = (2, 4) if _FULL else (1, 2)    # save / main mesh
_MESH_B = (4, 2) if _FULL else (2, 1)    # elastic-restore mesh


def _run(body: str) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={_DEVICES}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        MESH_A = {_MESH_A!r}
        MESH_B = {_MESH_B!r}
    """) + textwrap.dedent(body)
    # Inherit the parent environment: a stripped env (the original
    # hermetic {PYTHONPATH, PATH, HOME}) drops JAX_PLATFORMS=cpu, and
    # jax's platform probing then stalls for minutes per subprocess —
    # that, not compile time, was what blew the 420 s budget on the CI
    # container.  Force the cpu platform either way.
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)    # the script pins its own device count
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420, env=env)
    assert proc.returncode == 0, f"STDOUT:{proc.stdout}\nERR:{proc.stderr}"
    return proc.stdout


def test_moe_ep_matches_dense():
    """shard_map expert-parallel MoE == dense one-hot MoE (no drops)."""
    out = _run("""
        import dataclasses
        from repro.models.moe import (MoESpec, moe_defs, apply_moe,
                                      apply_moe_ep)
        from repro.models.params import init_params

        mesh = make_mesh(MESH_A, ("data", "model"))
        s = MoESpec(d_model=32, n_experts=8, top_k=2, d_ff=64,
                    capacity_factor=8.0, ep_axis="model")
        p = init_params(moe_defs(s), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

        dense_out, dense_aux = apply_moe(p, x, s)

        def f(pl, xl):
            out, aux = apply_moe_ep(pl, xl, s)
            return out, jax.lax.pmean(aux, ("data", "model"))
        w_specs = {k: (P() if k.startswith(("router", "shared"))
                       else P("model", None, None)) for k in p}
        ep_out, ep_aux = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(w_specs, P("data", "model", None)),
            out_specs=(P("data", "model", None), P()),
            check_vma=False))(p, x)
        err = float(jnp.abs(dense_out - ep_out).max())
        # EP routes per-shard (local top-k == global top-k for the same
        # tokens); with no capacity drops outputs must match exactly
        print("err", err)
        assert err < 1e-4, err
    """)
    assert "err" in out


def test_moe_tp_matches_dense():
    """Expert-TP path (ff-sharded experts) == dense path."""
    _run("""
        from repro.models.moe import (MoESpec, moe_defs, apply_moe,
                                      apply_moe_tp)
        from repro.models.params import init_params

        mesh = make_mesh(MESH_A, ("data", "model"))
        s = MoESpec(d_model=32, n_experts=6, top_k=2, d_ff=64,
                    capacity_factor=8.0, ep_axis="model")
        p = init_params(moe_defs(s), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        dense_out, _ = apply_moe(p, x, s)

        def f(pl, xl):
            out, aux = apply_moe_tp(pl, xl, s)
            return out, jax.lax.pmean(aux, ("data", "model"))
        w_specs = {}
        for k in p:
            if k.startswith(("router", "shared")):
                w_specs[k] = P()
            elif k == "wo":
                w_specs[k] = P(None, "model", None)
            else:
                w_specs[k] = P(None, None, "model")
        tp_out, _ = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(w_specs, P("data", None, None)),
            out_specs=(P("data", None, None), P()),
            check_vma=False))(p, x)
        err = float(jnp.abs(dense_out - tp_out).max())
        assert err < 1e-4, err
    """)


def test_sharded_train_step_runs():
    """A real (executed, not just lowered) sharded train step on the
    scaled mesh with a reduced config: loss decreases over a few steps."""
    _run("""
        from repro.configs import get_smoke_config, build_model
        from repro.train.optim import AdamWConfig
        from repro.train.step import build_train_step, init_train_state
        from repro.models.config import ShapeSpec

        mesh = make_mesh(MESH_A, ("data", "model"))
        cfg = get_smoke_config("granite-8b")
        model = build_model(cfg)
        shape = ShapeSpec("t", 32, 4, "train")
        step_fn, s_specs, b_specs = build_train_step(
            model, cfg, shape, mesh, AdamWConfig(lr=1e-2, warmup_steps=1,
                                                 total_steps=20))
        state = init_train_state(model, cfg, AdamWConfig(),
                                 jax.random.PRNGKey(0))
        state = jax.device_put(
            state, jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                s_specs))
        jit_step = jax.jit(step_fn, donate_argnums=(0,))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 cfg.vocab)
        batch = jax.device_put(
            {"tokens": tok, "labels": tok},
            jax.tree.map(lambda sp: NamedSharding(mesh, sp), b_specs))
        losses = []
        for _ in range(8):
            state, metrics = jit_step(state, batch)
            losses.append(float(metrics["loss"]))
        print("losses", losses[0], losses[-1])
        assert losses[-1] < losses[0], losses
    """)


def test_elastic_checkpoint_reshard():
    """Save on one mesh, restore onto its transpose — elastic restart."""
    _run("""
        import tempfile
        from repro.ckpt.checkpoint import (save_checkpoint,
                                           restore_checkpoint)
        mesh_a = make_mesh(MESH_A, ("data", "model"))
        mesh_b = make_mesh(MESH_B, ("data", "model"))
        w = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh_a, P("data", "model")))
        state = {"params": {"w": w}}
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, state)
        restored = restore_checkpoint(
            d, 1, state, mesh=mesh_b,
            specs={"params": {"w": P("data", "model")}})
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                                   np.asarray(w))
        shard_shape = restored["params"]["w"].sharding.shard_shape((8, 8))
        expect = (8 // MESH_B[0], 8 // MESH_B[1])
        assert shard_shape == expect, (shard_shape, expect)
    """)
