"""Expert-parallel MoE on 8 simulated devices: the deepseek-style
shard_map path (route -> all_to_all -> grouped GEMM -> all_to_all) with
ADSALA tuning the expert GEMM tiles.

Run:  PYTHONPATH=src python examples/moe_expert_parallel.py
(sets its own XLA device-count flag; run as its own process)
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.models.moe import MoESpec, apply_moe, apply_moe_ep, moe_defs
from repro.models.params import init_params


def main() -> None:
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MoESpec(d_model=64, n_experts=8, top_k=2, d_ff=128,
                   capacity_factor=2.0, ep_axis="model")
    params = init_params(moe_defs(spec), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 64))

    def f(p, xl):
        out, aux = apply_moe_ep(p, xl, spec)
        return out, jax.lax.pmean(aux, ("data", "model"))

    w_specs = {k: (P() if k.startswith(("router", "shared"))
                   else P("model", None, None)) for k in params}
    ep = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(w_specs, P("data", "model", None)),
        out_specs=(P("data", "model", None), P()), check_vma=False))

    out, aux = ep(params, x)
    ref, _ = apply_moe(params, x, spec)
    err = float(jnp.abs(out - ref).max())
    print(f"[moe-ep] out {out.shape}, aux={float(aux):.4f}, "
          f"max|EP - dense| = {err:.2e}")

    # what the collective schedule looks like
    hlo = ep.lower(params, x).compile().as_text()
    n_a2a = hlo.count(" all-to-all")
    print(f"[moe-ep] compiled with {n_a2a} all-to-all ops "
          f"(dispatch + return per MoE layer)")
    print("[moe-ep] OK" if err < 1e-3 else "[moe-ep] MISMATCH")


if __name__ == "__main__":
    main()
