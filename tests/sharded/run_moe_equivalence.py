"""Subprocess script: MoE block numerical equivalence across all plans/algos
AND both dispatch modes on CPU meshes (fused RS-A2A-AG must be exact, not
approximate).

capacity runs with cf=8.0 (ample — no drops — so the distributed and local
paths see identical slot sets); dropless has no capacity to trip over and
must match the local oracle under every layout by construction."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.partitioner import make_plan
from repro.launch.mesh import make_mesh
from repro.models import moe as M
from repro.models.param import init_tree


def main():
    cfg = ModelConfig(name="tiny-moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      n_experts=8, top_k=2, d_expert=96, n_shared_experts=1)
    key = jax.random.PRNGKey(0)
    params = init_tree(key, M.moe_spec(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)

    meshes = {
        "2x2": make_mesh((2, 2), ("data", "model")),
        "2x4": make_mesh((2, 4), ("data", "model")),
        "4x2": make_mesh((4, 2), ("data", "model")),
        "pod2x2x2": make_mesh((2, 2, 2), ("pod", "data", "model")),
    }
    cases = [("mixserve", "fused"), ("mixserve", "sync"),
             ("mixserve", "unfused"), ("dp_ep", "unfused"),
             ("pure_tp", "unfused")]
    for mode in ("capacity", "dropless"):
        out_local, _ = M.moe_local(params, x, cfg, cf=8.0, dispatch=mode)
        for mesh_name, mesh in meshes.items():
            for strat, algo in cases:
                plan = make_plan(strat, mesh, comm_algo=algo, dispatch=mode)
                out, _ = jax.jit(
                    lambda p, xx: M.moe_block(p, xx, cfg, plan, cf=8.0))(
                        params, x)
                err = float(jnp.max(jnp.abs(out - out_local)))
                print(f"{mode:8s} {mesh_name:9s} {strat:9s} {algo:8s} "
                      f"err={err:.2e}")
                assert err < 1e-4, (mode, mesh_name, strat, algo, err)

    # Mixed-batch safety under shard_map (the unified serving step's (B,
    # chunk) buffers): dropless rows must be invariant to extra pad slots
    # riding in the same batch — count-independence must survive the EP
    # counts A2A / ragged exchange / closed-form regroup, fused and unfused.
    x_mixed = x[:2, :4]                                     # (2, 4, 64)
    garbage = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 64),
                                jnp.float32) * 3.0
    x_padded = jnp.concatenate([x_mixed, garbage], axis=0)  # (4, 4, 64)
    for strat, algo in [("mixserve", "fused"), ("mixserve", "unfused"),
                        ("dp_ep", "unfused")]:
        plan = make_plan(strat, meshes["2x2"], comm_algo=algo,
                         dispatch="dropless")
        fn = jax.jit(lambda p, xx, _plan=plan:
                     M.moe_block(p, xx, cfg, _plan)[0])
        real = fn(params, x_mixed)
        padded = fn(params, x_padded)[:2]
        err = float(jnp.max(jnp.abs(padded - real)))
        print(f"mixed-batch dropless {strat:9s} {algo:8s} err={err:.2e}")
        assert err < 1e-5, ("mixed", strat, algo, err)
    print("MOE_EQUIVALENCE_OK")


if __name__ == "__main__":
    main()
