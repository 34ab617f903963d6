"""Subprocess script: the serving Engine on a 2x2 CPU mesh.

``LLM.from_spec(..., mesh=mesh)`` places every weight by the plan's
shardings and serves through the unified step — the paged flash kernel
under ``shard_map``, the MoE kernels inside the EP/TP shard_map bodies —
for mixserve, pure_tp and pure_ep.  Each greedy stream must equal the
unsharded engine's (float32, interpret-mode kernels)."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.kernels.policy import KernelPolicy
from repro.launch.mesh import make_mesh
from repro.models.model import init_params
from repro.serving.api import LLM, ServeSpec


def main():
    cfg = C.get_reduced("phi3.5-moe-42b")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 19)]
    mesh = make_mesh((2, 2), ("data", "model"))
    spec = dict(max_batch=4, max_len=64, prompt_len=19, max_new_tokens=4,
                kernels=KernelPolicy.all_on())
    want = LLM.from_spec(ServeSpec(**spec), cfg=cfg,
                         params=params).generate(prompts, 4)
    for strategy in ("mixserve", "pure_tp", "pure_ep"):
        llm = LLM.from_spec(ServeSpec(strategy=strategy, **spec), cfg=cfg,
                            params=params, mesh=mesh)
        spans = {len(x.sharding.device_set)
                 for x in jax.tree.leaves(llm.params)}
        assert spans == {4}, (strategy, spans)
        w_in = llm.params["groups"][0]["moe"]["w_in"]
        assert w_in.sharding.shard_shape(w_in.shape) != w_in.shape, strategy
        got = llm.generate(prompts, 4)
        print(f"{strategy:9s} tp={llm.spec.plan.tp} ep={llm.spec.plan.ep} "
              f"{got}")
        assert got == want, (strategy, got, want)
    print("ENGINE_MESH_OK")


if __name__ == "__main__":
    main()
