"""Subprocess script: distributed-path kernel equivalence on a CPU mesh.

``moe_block`` under every plan/comm-algo with ``KernelPolicy.all_on()`` must
match (a) the same plan with kernels off and (b) the local oracle — AND the
kernelized jitted graph must actually trace the kernels (ops.counters,
incremented at trace time).  Both dispatch modes are covered: capacity
traces moe_gemm + the fused permute/unpermute pair; dropless traces
grouped_gemm + the segment-aware ragged permute + unpermute."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.partitioner import make_plan
from repro.kernels import ops
from repro.kernels.policy import KernelPolicy
from repro.launch.mesh import make_mesh
from repro.models import moe as M
from repro.models.param import init_tree

REQUIRED = {
    "capacity": ("topk_gate", "moe_gemm", "permute_tokens",
                 "unpermute_tokens"),
    "dropless": ("topk_gate", "grouped_gemm", "unpermute_tokens"),
}


def required(mode: str, strat: str) -> tuple:
    req = REQUIRED[mode]
    if mode == "dropless" and strat != "pure_tp":
        # the segment-aware ragged permute only runs on the EP exchange
        # paths (pure_tp has no EP: the local gather is a plain permute)
        req = req + ("permute_tokens_ragged",)
    return req


def main():
    cfg = ModelConfig(name="tiny-moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      n_experts=8, top_k=2, d_expert=96, n_shared_experts=1)
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)

    mesh = make_mesh((2, 4), ("data", "model"))
    cases = [("mixserve", "fused"), ("mixserve", "unfused"),
             ("dp_ep", "unfused"), ("pure_tp", "unfused")]
    for mode in ("capacity", "dropless"):
        out_local, _ = M.moe_local(params, x, cfg, cf=8.0, dispatch=mode)
        for strat, algo in cases:
            p_off = make_plan(strat, mesh, comm_algo=algo,
                              kernels=KernelPolicy.off(), dispatch=mode)
            p_on = make_plan(strat, mesh, comm_algo=algo,
                             kernels=KernelPolicy.all_on(), dispatch=mode)
            off, _ = jax.jit(
                lambda p, xx: M.moe_block(p, xx, cfg, p_off, cf=8.0))(
                    params, x)
            ops.reset_counters()
            on, _ = jax.jit(
                lambda p, xx: M.moe_block(p, xx, cfg, p_on, cf=8.0))(
                    params, x)
            missing = [k for k in required(mode, strat)
                       if ops.counters[k] == 0]
            assert not missing, (mode, strat, algo, missing,
                                 dict(ops.counters))
            err = float(jnp.max(jnp.abs(on - off)))
            err_l = float(jnp.max(jnp.abs(on - out_local)))
            print(f"{mode:8s} {strat:9s} {algo:8s} on-vs-off={err:.2e} "
                  f"vs-local={err_l:.2e} counters={dict(ops.counters)}")
            assert err < 1e-4 and err_l < 1e-4, (mode, strat, algo, err,
                                                 err_l)

    # Regression: force tiny permute tiles (bn=8 << the 128-row exchange
    # buffers) so the ragged kernels actually elide tiles.  At default
    # block sizes one tile covers the whole buffer and elision never
    # fires — which masked a bug where the EP send gather's validity was
    # described as one contiguous prefix instead of per-destination-rank
    # prefixes, zeroing every row bound for ranks >= 1.
    from repro.kernels import autotune
    autotune.clear_cache()
    autotune.register("permute", (128, 64), jnp.float32, {"bn": 8},
                      persist=False)
    out_local, _ = M.moe_local(params, x, cfg, dispatch="dropless")
    for strat, algo in (("mixserve", "fused"), ("mixserve", "unfused"),
                        ("dp_ep", "unfused")):
        p_on = make_plan(strat, mesh, comm_algo=algo,
                         kernels=KernelPolicy.all_on(), dispatch="dropless")
        on, _ = jax.jit(
            lambda p, xx: M.moe_block(p, xx, cfg, p_on))(params, x)
        err = float(jnp.max(jnp.abs(on - out_local)))
        print(f"small-bn {strat:9s} {algo:8s} vs-local={err:.2e}")
        assert err < 1e-4, ("small-bn", strat, algo, err)
    autotune.clear_cache()
    print("MOE_KERNEL_EQUIVALENCE_OK")


if __name__ == "__main__":
    main()
