"""Subprocess script: full-model forward/train/decode under a sharding plan
equals the unsharded reference, on a (2 data x 2 model) CPU mesh.

Covers: dense GQA (smollm), MLA+MoE (deepseek reduced), hybrid (rgemma),
decode with kv_seq sharding over the model axis, per-slot lengths.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.core.partitioner import make_plan
from repro.launch.mesh import make_mesh
from repro.models import model as M

# Per-arch max-abs-error gates.  The default is the strict 2e-4 every arch
# held at the seed; whisper-tiny sits at ~3e-3 for a ROOT-CAUSED reason
# (investigated PR 7, see ROADMAP): the sharded encoder itself is clean
# (1.2e-5), but the reduced random-init config is intrinsically
# ill-conditioned at this seed — the UNSHARDED forward amplifies a 1e-7
# input perturbation into ~9e-2 logits at the same worst token position
# (local amplification ~1e5-1e6), so the sharded run's different fp32
# reduction order alone explains 3e-3 (greedy argmax is unaffected).
# The loose gate stays as the regression tripwire: green at the
# conditioning-limited error, red only if the sharded path regresses.
DEFAULT_TOL = 2e-4
TOLERANCES = {
    "whisper-tiny": 5e-3,    # conditioning-limited, not a sharding bug
}


def check(arch, mesh, plan_name="mixserve"):
    import dataclasses
    cfg = C.get_reduced(arch)
    if cfg.is_moe:
        # ample capacity: sharded routing computes per-DP-rank capacities, so
        # with the default factor token DROPS differ from the global oracle
        # (the paper's Fig. 6c trade-off) — equivalence needs no drops.
        # (Moot under the default dropless dispatch, but the capacity knob
        # stays pinned so a dispatch="capacity" A/B keeps passing too.)
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    plan = make_plan(plan_name, mesh)
    b, s_pre, n_dec, max_len = 4, 16, 3, 64
    kw = {}
    if cfg.frontend == "vision_stub":
        kw["embeds"] = jax.random.normal(
            jax.random.PRNGKey(9), (b, cfg.n_frontend_tokens, cfg.d_model)
        ) * 0.02
    if cfg.frontend == "audio_stub":
        e = cfg.encoder
        kw["frames"] = jax.random.normal(
            jax.random.PRNGKey(9), (b, e.n_frames, e.d_model)) * 0.02
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s_pre + n_dec),
                                0, cfg.vocab_size)

    # ---- unsharded reference ----
    ref = M.forward(params, cfg, tokens=tokens, **kw)

    # ---- sharded train-mode forward ----
    with mesh:
        got = jax.jit(lambda p, t: M.forward(p, cfg, plan, tokens=t, **kw))(
            params, tokens)
    err_f = float(jnp.max(jnp.abs(got.logits - ref.logits)))

    # ---- sharded prefill + decode with per-slot lengths ----
    with mesh:
        cache = M.init_cache(cfg, b, max_len, jnp.float32)
        pre = jax.jit(lambda p, t, c: M.forward(p, cfg, plan, tokens=t,
                                                cache=c, **kw))(
            params, tokens[:, :s_pre], cache)
        cache = pre.cache
        # switch to per-slot vector lengths (continuous-batching form)
        cache = {**cache,
                 "length": jnp.full((b,), int(cache["length"]), jnp.int32)}
        errs = []
        dec = jax.jit(lambda p, t, c: M.forward(p, cfg, plan, tokens=t,
                                                cache=c))
        front = cfg.n_frontend_tokens if cfg.frontend == "vision_stub" else 0
        for i in range(n_dec):
            out = dec(params, tokens[:, s_pre + i:s_pre + i + 1], cache)
            cache = out.cache
            errs.append(float(jnp.max(jnp.abs(
                out.logits[:, 0] - ref.logits[:, front + s_pre + i]))))
    tol = TOLERANCES.get(arch, DEFAULT_TOL)
    note = "" if tol == DEFAULT_TOL else f"  (known-loose tol={tol:g})"
    print(f"{arch:22s} fwd_err={err_f:.2e} decode_errs="
          f"{['%.1e' % e for e in errs]}{note}")
    assert err_f < tol, (arch, err_f, tol)
    assert max(errs) < tol, (arch, errs, tol)


def main():
    mesh = make_mesh((2, 2), ("data", "model"))
    for arch in ("smollm-360m", "deepseek-v2-236b", "phi3.5-moe-42b",
                 "recurrentgemma-9b", "rwkv6-1.6b", "qwen2-vl-7b",
                 "whisper-tiny", "minicpm3-4b"):
        check(arch, mesh)
    # multi-pod mini mesh on the paper-representative arch
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    check("deepseek-v2-236b", mesh3)
    check("phi3.5-moe-42b", mesh3, plan_name="dp_ep")
    print("SHARDED_MODEL_OK")


if __name__ == "__main__":
    main()
