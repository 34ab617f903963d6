"""Subprocess script: the micro-chunked, count-bounded EP exchange is
BIT-IDENTICAL to the monolithic dropless exchange on CPU meshes.

Covers C in {1, 2, 4} x {fused, unfused} x {pure chunking (cap=worst-case),
auto cap, tight explicit cap}, the token-sliced dp_ep layout, a kernels-on
lane (interpret-mode Pallas), and an adversarial all-tokens-to-one-rank
skew that overflows a tight cap and must take the worst-case-extent
fallback (rank-uniform lax.cond) without changing a single bit."""

import os
# XLA:CPU's YNNPACK dot fusions pick their accumulation order by row count,
# so the same row's product differs between a chunk-sized and a whole-batch
# GEMM; the plain dot emitter computes each row the same way at any row
# count, which is what a bit-for-bit comparison of two schedules needs.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_experimental_ynn_fusion_type=")

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.cost_model import EpOverlap, cap_rows_for
from repro.core.partitioner import make_plan
from repro.kernels.policy import KernelPolicy
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
        "2x4": make_mesh((2, 4), ("data", "model")),   # ep=2, tp=4
        "4x2": make_mesh((4, 2), ("data", "model")),   # ep=4, tp=2
    }

    def run(mesh, strat, algo, ovl, p=params, xx=x, kernels=None):
        plan = make_plan(strat, mesh, comm_algo=algo, dispatch="dropless",
                         kernels=kernels, ep_overlap=ovl)
        return jax.jit(lambda pp, xv: M.moe_block(pp, xv, cfg, plan)[0])(
            p, xx)

    overlaps = [
        EpOverlap(chunks=1, cap_rows=0),    # count-bounding alone
        EpOverlap(chunks=2, cap_rows=-1),   # chunking alone (worst-case cap)
        EpOverlap(chunks=2, cap_rows=0),    # chunk + auto cap
        EpOverlap(chunks=4, cap_rows=0),
        EpOverlap(chunks=2, cap_rows=8),    # tight explicit cap
        EpOverlap(chunks=4, cap_rows=8),
    ]
    for mesh_name, mesh in meshes.items():
        for strat, algo in [("mixserve", "fused"), ("mixserve", "unfused")]:
            base = run(mesh, strat, algo, None)
            for ovl in overlaps:
                out = run(mesh, strat, algo, ovl)
                err = float(jnp.max(jnp.abs(out - base)))
                print(f"{mesh_name:5s} {strat:9s} {algo:8s} "
                      f"{ovl.describe():28s} err={err:.1e}")
                assert err == 0.0, (mesh_name, strat, algo, ovl, err)

    # token-sliced pure-EP layout (every device its own EP rank)
    base = run(meshes["2x4"], "dp_ep", "unfused", None)
    for ovl in (EpOverlap(chunks=2, cap_rows=0),
                EpOverlap(chunks=2, cap_rows=4)):
        out = run(meshes["2x4"], "dp_ep", "unfused", ovl)
        err = float(jnp.max(jnp.abs(out - base)))
        print(f"dp_ep  token-sliced {ovl.describe():28s} err={err:.1e}")
        assert err == 0.0, ("dp_ep", ovl, err)

    # kernels-on lane: interpret-mode Pallas permute/gemm under chunking
    kp = KernelPolicy.all_on()
    base = run(meshes["2x4"], "mixserve", "fused", None, kernels=kp)
    out = run(meshes["2x4"], "mixserve", "fused",
              EpOverlap(chunks=2, cap_rows=8), kernels=kp)
    err = float(jnp.max(jnp.abs(out - base)))
    print(f"kernels-on fused C=2 cap=8            err={err:.1e}")
    assert err == 0.0, ("kernels", err)

    # ---- adversarial skew: every token routes to rank 0's experts, so the
    # per-(source, dest) segment count == n_chunk >> cap -> the overflow
    # fallback must fire on every rank and stay bit-identical ----
    router_skew = jnp.zeros_like(params["router"])
    router_skew = router_skew.at[:, 0].set(10.0).at[:, 1].set(9.0)
    p_skew = {**params, "router": router_skew}
    # confirm the cap really is exceeded (fallback genuinely exercised):
    # ep=2 -> experts 0,1 live on rank 0; all t*k slots target rank 0.
    t_local, k = (x.shape[0] // 2) * x.shape[1], cfg.top_k
    n_c = t_local * k // 2                                    # C=2
    ovl = EpOverlap(chunks=2, cap_rows=8)
    assert cap_rows_for(n_c, 2, ovl) < n_c, "skew case must overflow the cap"
    for algo in ("fused", "unfused"):
        base = run(meshes["2x4"], "mixserve", algo, None, p=p_skew)
        out = run(meshes["2x4"], "mixserve", algo, ovl, p=p_skew)
        err = float(jnp.max(jnp.abs(out - base)))
        print(f"skew-overflow {algo:8s} C=2 cap=8       err={err:.1e}")
        assert err == 0.0, ("skew", algo, err)

    print("OVERLAP_EQUIVALENCE_OK")


if __name__ == "__main__":
    main()
