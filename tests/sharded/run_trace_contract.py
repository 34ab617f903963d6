"""Subprocess script: the trace contract on real CPU meshes.

For each strategy/algorithm/overlap the collective census of the traced
``moe_block`` must equal ``cost_model.comm_census`` exactly — and a
deliberately sabotaged block (one extra all-reduce) must FAIL, so a pass
is never vacuous."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax

from repro.analysis import trace_contract as TC
from repro.configs.base import ModelConfig
from repro.core.cost_model import EpOverlap
from repro.core.partitioner import make_plan
from repro.launch.mesh import make_mesh
from repro.models import moe as M


def main():
    cfg = ModelConfig(name="tiny-moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      n_experts=8, top_k=2, d_expert=96, n_shared_experts=1)
    meshes = {
        "2x4": make_mesh((2, 4), ("data", "model")),
        "4x2": make_mesh((4, 2), ("data", "model")),
    }
    cases = [("mixserve", "fused"), ("mixserve", "sync"),
             ("mixserve", "unfused"), ("dp_ep", "unfused"),
             ("pure_tp", "unfused")]
    overlaps = {"mono": None, "chunks2": EpOverlap(chunks=2),
                # explicit small cap: exercises the overflow guard and the
                # conditional worst-case fallback inside lax.cond
                "cap8": EpOverlap(chunks=2, cap_rows=8)}

    n_checked = n_conditional = 0
    for mesh_name, mesh in meshes.items():
        for strat, algo in cases:
            for ovl_name, ovl in overlaps.items():
                plan = make_plan(strat, mesh, comm_algo=algo,
                                 dispatch="dropless", ep_overlap=ovl)
                tag = f"{mesh_name}/{strat}/{algo}/{ovl_name}"
                r = TC.check_moe_census(cfg, plan, name=tag)
                print(r)
                assert r.ok, r
                n_checked += 1
                if r.expected["conditional"]:
                    n_conditional += 1
    assert n_conditional > 0, \
        "no case exercised the conditional overflow fallback"

    # purity of the lowered hybrid program: no host callbacks, no
    # dynamic shapes
    plan = make_plan("mixserve", meshes["2x4"], comm_algo="fused",
                     dispatch="dropless")
    r = TC.check_moe_purity(cfg, plan)
    print(r)
    assert r.ok, r

    # negative control: one extra all-reduce smuggled into the block must
    # produce a census mismatch (the acceptance criterion's scratch test)
    orig = M.moe_block
    mesh = meshes["2x4"]

    def sabotaged(p, x, cfg, plan, **kw):
        from jax.sharding import PartitionSpec as P
        out = orig(p, x, cfg, plan, **kw)
        extra = jax.shard_map(lambda y: jax.lax.psum(y, "model"), mesh=mesh,
                              in_specs=P(), out_specs=P(),
                              check_vma=False)(out[0].sum())
        return (out[0] + 0 * extra,) + tuple(out[1:])

    M.moe_block = sabotaged
    try:
        r = TC.check_moe_census(cfg, plan, name="sabotaged")
    finally:
        M.moe_block = orig
    print(r)
    assert not r.ok, "extra all-reduce was NOT detected"
    assert any("all_reduce(model)" in m for m in r.mismatches), r.mismatches

    print(f"checked={n_checked} conditional_cases={n_conditional}")
    print("TRACE_CONTRACT_OK")


if __name__ == "__main__":
    main()
