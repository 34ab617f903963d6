"""Pallas kernels integrated into the MoE block: kernel path == jnp path,
locally (in-process) and on a CPU mesh (subprocess, fake devices)."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.partitioner import NULL_PLAN
from repro.kernels import ops
from repro.kernels.policy import KernelPolicy
from repro.models import model as MD
from repro.models import moe as M
from repro.models.param import init_tree

CFG = ModelConfig(name="k-moe", family="moe", n_layers=1, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  n_experts=4, top_k=2, d_expert=96, n_shared_experts=1)

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")
ROOT = os.path.dirname(os.path.abspath(HERE))


@pytest.mark.kernels
@pytest.mark.parametrize("dispatch", ["capacity", "dropless"])
def test_moe_local_kernel_path_matches_jnp(dispatch):
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(CFG), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    out_jnp, aux_jnp = M.moe_local(params, x, CFG, cf=8.0, use_kernels=False,
                                   dispatch=dispatch)
    out_krn, aux_krn = M.moe_local(params, x, CFG, cf=8.0, use_kernels=True,
                                   dispatch=dispatch)
    np.testing.assert_allclose(np.asarray(out_krn), np.asarray(out_jnp),
                               atol=2e-5)
    assert abs(float(aux_krn) - float(aux_jnp)) < 1e-5


@pytest.mark.kernels
@pytest.mark.parametrize("dispatch,required", [
    ("capacity", ("topk_gate", "moe_gemm", "permute_tokens",
                  "unpermute_tokens")),
    ("dropless", ("topk_gate", "grouped_gemm", "permute_tokens",
                  "unpermute_tokens")),
])
def test_moe_local_policy_traces_all_kernels(dispatch, required):
    """KernelPolicy.all_on() must actually put every hot-path kernel into the
    jitted MoE graph (trace-time counters), and match jnp to allclose."""
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(CFG), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    fn_off = jax.jit(lambda p, xx: M.moe_local(p, xx, CFG, cf=8.0,
                                               dispatch=dispatch))
    fn_on = jax.jit(lambda p, xx: M.moe_local(
        p, xx, CFG, cf=8.0, policy=KernelPolicy.all_on(), dispatch=dispatch))
    out_off, _ = fn_off(params, x)
    ops.reset_counters()
    out_on, _ = fn_on(params, x)
    for k in required:
        assert ops.counters[k] > 0, (k, dict(ops.counters))
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               atol=2e-5)


@pytest.mark.kernels
def test_moe_capacity_factor_zero_not_silently_replaced():
    """cf=0.0 is a real (degenerate) capacity factor: capacity clamps to 1
    and must NOT fall back to cfg.capacity_factor (the old `cf or ...` bug).
    Capacity dispatch is pinned — the dropless default has no capacity."""
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(CFG), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    out_zero, _ = M.moe_local(params, x, CFG, cf=0.0, dispatch="capacity")
    out_eps, _ = M.moe_local(params, x, CFG, cf=1e-9,  # same capacity (1)
                             dispatch="capacity")
    out_default, _ = M.moe_local(params, x, CFG,       # cfg.capacity_factor
                                 dispatch="capacity")
    np.testing.assert_allclose(np.asarray(out_zero), np.asarray(out_eps),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(out_zero - out_default))) > 1e-4


@pytest.mark.parametrize("conf", ["phi3.5-moe-42b-l4", "deepseek-v2-lite-l9"])
def test_unified_step_reads_expert_stacks_in_place(conf):
    """The unified serving step of each benchmark configuration, traced at
    its published widths over abstract params (nothing computed): every
    ``grouped_gemm`` call site reads the layer-stacked experts in place
    and none pads them."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import arch

    a = arch.arch_from(arch.load_config(f"bench/configs/{conf}.json"))
    cfg = arch.program_config(a)
    off = {k: False for k in a.overrides.get("kernels_off", ())}
    plan = dataclasses.replace(
        NULL_PLAN, kernels=dataclasses.replace(KernelPolicy.all_on(), **off))
    assert M.reads_stack(plan)
    b, chunk, max_len, page = 8, 64, 3584, 16
    params = MD.abstract_params(cfg, jnp.bfloat16)
    cache = jax.eval_shape(lambda: MD.init_paged_cache(
        cfg, b, max_len, page_size=page, pool_pages=b * max_len // page))
    ops.reset_counters()
    jax.make_jaxpr(lambda p, t, c, q: MD.forward(
        p, cfg, plan, tokens=t, cache=c, q_lens=q, last_only=True,
        expert_stats=True))(params, jax.ShapeDtypeStruct((b, chunk), jnp.int32),
                            cache, jax.ShapeDtypeStruct((b,), jnp.int32))
    # one scan body traces the MoE layers' three GEMMs once
    assert ops.counters["grouped_gemm"] == 3, dict(ops.counters)
    assert ops.counters["grouped_gemm_stacked"] == 3
    assert ops.counters["grouped_gemm_w_pad"] == 0


@pytest.mark.parametrize("via_block", [False, True])
def test_moe_block_cf_zero(via_block):
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(CFG), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    if via_block:
        out, _ = M.moe_block(params, x, CFG, cf=0.0, dispatch="capacity")
    else:
        out, _ = M.moe_local(params, x, CFG, cf=0.0, dispatch="capacity")
    ref, _ = M.moe_local(params, x, CFG, cf=1e-9, dispatch="capacity")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_moe_block_distributed_kernel_equivalence():
    """moe_block on a CPU mesh: KernelPolicy on vs off allclose across the
    mixserve/dp_ep/pure_tp plans, with the kernels asserted traced.  Runs in
    a subprocess with its own fake-device count (dry-run isolation rule)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "sharded", "run_moe_kernel_equivalence.py")],
        capture_output=True, text=True, timeout=1200, env=env)
    if r.returncode != 0:
        pytest.fail(f"run_moe_kernel_equivalence.py failed:\n"
                    f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}")
    assert "MOE_KERNEL_EQUIVALENCE_OK" in r.stdout


@pytest.mark.kernels
def test_route_topk_kernel_matches_jnp():
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 8), jnp.float32)
    i1, w1, a1 = M.route_topk(logits, 3, use_kernel=False)
    i2, w2, a2 = M.route_topk(logits, 3, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    assert abs(float(a1) - float(a2)) < 1e-6


@pytest.mark.kernels
def test_expert_ffn_kernel_matches_jnp():
    params = init_tree(jax.random.PRNGKey(0), M.moe_spec(CFG), jnp.float32)
    buf = jax.random.normal(jax.random.PRNGKey(3), (4, 24, 64), jnp.float32)
    a = M.expert_ffn(params, buf, CFG, use_kernel=False)
    b = M.expert_ffn(params, buf, CFG, use_kernel=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
