"""Smoke run of the serving path on a TPU: phi3.5-moe-42b at its published
widths, cut in depth, with random bf16 weights drawn from a seed.

    python chip_smoke.py               # one chip, 4 layers, every kernel native
    python chip_smoke.py --four-chips  # 2x2 host: mixserve / pure_tp / pure_ep

One process drives the chip(s); no child process touches JAX.  Phases print
one line each, in order, and any failed phase exits non-zero.  The last line
of standard output is one JSON object naming the device, printed only when
every phase passed.  Without a TPU (``JAX_PLATFORMS=cpu``, or this file
outside its checkout) the script exits non-zero and prints no result.

Correctness is judged on logits, teacher-forced: the serving engine's logit
row for every token it emitted is compared with ``model.forward``'s full
pass over the same tokens in float32 at the highest matmul precision, one
layer deep.  The engine under test (Pallas kernels on, or sharded over four
chips) must be as close to that reference as the one-chip jnp engine it
replaces; the constants below state the tolerance and why.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "phi3.5-moe-42b"
LAYERS = 4             # one chip: 4 x (2.52 GB experts + 84 MB attention)
FOUR_CHIP_LAYERS = GATE_LAYERS = 1  # the depth the logit gate holds at
SEED = 0
PROMPT_LENS = (64, 96, 160, 256, 384, 512)
MAX_NEW = 24
CHECK_PROMPTS = 3      # requests re-served for the logit comparison
KERNELS = ("flash_chunk_paged", "topk_gate", "permute_tokens",
           "grouped_gemm", "unpermute_tokens")

# Tolerance of the logit comparison.  Both engines run in bf16, which keeps
# 8 significant bits, and round at different places (online vs full softmax,
# tiled vs whole GEMMs, the k-way combine, TP partial sums); on the chip the
# jnp path also rounds its elementwise ops to bf16, which the CPU backend
# does not.  So neither is the truth: the float32 forward pass is, and the
# engine under test passes when its median and 90th-percentile row errors
# (largest logit error over the row's largest logit) are within 1.5x of the
# one-chip jnp engine's, plus 2^-9 for rows both get almost exact.  A wrong
# page, token row or expert moves a row by O(1), far outside that.  The
# gate is one layer deep: with random weights each further layer multiplies
# a rounding difference, so at the served depth two correct bf16 engines
# already disagree on most greedy tokens; that comparison is printed only.
SLACK_X = 1.5
SLACK_ABS = 2.0 ** -9


class SmokeFailure(RuntimeError):
    pass


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def smoke_config(n_layers: int):
    import repro.configs as C
    return dataclasses.replace(C.get(ARCH), n_layers=n_layers)


def prompts_for(cfg, lens) -> list:
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def serve_spec(**kw):
    from repro.serving.api import ServeSpec
    base = dict(reduced=False, max_batch=len(PROMPT_LENS), max_len=1024,
                prompt_len=max(PROMPT_LENS), max_new_tokens=MAX_NEW,
                seed=SEED)
    base.update(kw)
    return ServeSpec(**base)


def served_logits(llm, prompts, max_new: int):
    """Serve ``prompts`` through the engine and keep, for every emitted
    token, the logit row it was sampled from: {rid: (tokens, rows)}."""
    from repro.serving.engine import Request

    eng = llm.engine
    reqs = [Request(rid=10_000 + i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    queue = list(reqs)
    rows: dict = {r.rid: [] for r in reqs}
    while queue or eng.n_active:
        while queue and eng.free_slots() and eng.admit(queue[0]):
            queue.pop(0)
        before = {i: (r, len(r.out_tokens)) for i, r in enumerate(eng.slots)
                  if r is not None}
        eng.step(llm.spec.token_budget)
        host = None
        for i, (r, n0) in before.items():
            if len(r.out_tokens) > n0:
                if host is None:
                    host = np.asarray(eng.last_logits, np.float32)
                rows[r.rid].append(host[i])
    for r in reqs:
        check(r.state == "DONE" and len(r.out_tokens) == max_new,
              f"request {r.rid} ended {r.state} with {len(r.out_tokens)} "
              "tokens")
    return {r.rid: (r.prompt, np.asarray(r.out_tokens), np.stack(rows[r.rid]))
            for r in reqs}


def reference_logits(params, cfg, plan, served) -> dict:
    """Teacher-forced full pass over each request's prompt + output: the row
    predicting output token j is position len(prompt) - 1 + j.  Sequences
    are right-padded to one length (causal attention keeps the padding out
    of every earlier position), so one program covers them all."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import forward

    seqs = {rid: np.concatenate([p, out[:-1]])
            for rid, (p, out, _) in served.items()}
    width = max(len(s) for s in seqs.values())
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs.values()):
        toks[i, :len(s)] = s
    fwd = jax.jit(lambda p, t: forward(p, cfg, plan, tokens=t).logits)
    logits = np.asarray(fwd(params, jnp.asarray(toks)).astype(jnp.float32))
    ref = {}
    for i, (rid, (p, out, _)) in enumerate(served.items()):
        lo = len(p) - 1
        ref[rid] = logits[i, lo:lo + len(out), :cfg.vocab_size]
    return ref


def compare(served, ref, vocab: int) -> dict:
    """Per-row relative error max|got - ref| / max|ref|, summarized."""
    rel, agree = [], []
    for rid, (_, _, got) in served.items():
        got = got[:, :vocab]
        want = ref[rid]
        check(bool(np.isfinite(got).all()), f"request {rid}: non-finite "
              "served logits")
        err = np.abs(got - want).max(-1) / np.abs(want).max(-1)
        rel.extend(err.tolist())
        agree.extend((got.argmax(-1) == want.argmax(-1)).tolist())
    rel = np.asarray(rel)
    return {"rows": int(rel.size), "median": float(np.median(rel)),
            "p90": float(np.quantile(rel, 0.9)), "max": float(rel.max()),
            "top1": float(np.mean(agree))}


def describe_stats(s: dict) -> str:
    return (f"{s['rows']} rows: median {s['median']:.2e}, p90 "
            f"{s['p90']:.2e}, max {s['max']:.2e}, top-1 agreement "
            f"{s['top1']:.1%}")


def within(test: dict, base: dict) -> bool:
    return all(test[k] <= SLACK_X * base[k] + SLACK_ABS
               for k in ("median", "p90"))


def f32_reference(params, cfg, plan, served) -> dict:
    """``reference_logits`` in float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return reference_logits(p32, cfg, plan, served)


def kernel_calls_in(hlo: str) -> dict:
    """Kernel name -> number of TPU custom calls traced from its wrapper."""
    out = {k: 0 for k in KERNELS}
    for line in hlo.splitlines():
        if "tpu_custom_call" not in line:
            continue
        for k in KERNELS:
            if f"jit({k})/" in line:
                out[k] += 1
    return out


def unified_args(eng):
    """The arguments of the engine's next unified step, at their shapes."""
    import jax
    import jax.numpy as jnp
    b, c = eng.max_batch, eng.chunk
    return (eng.params, jnp.zeros((b, c), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            (eng.cache, eng._ecnt), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(dev, *, n_layers: int = LAYERS, prompt_lens=PROMPT_LENS,
                 max_new: int = MAX_NEW) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.policy import KernelPolicy
    from repro.serving.api import LLM

    cfg = smoke_config(n_layers)
    full = smoke_config(32)

    # ---- phase 2: resolved spec ----
    resolved = serve_spec(max_batch=len(prompt_lens),
                          prompt_len=max(prompt_lens),
                          max_new_tokens=max_new).resolve(cfg)
    say("2 spec", f"{cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.head_dim} experts={cfg.n_experts} top-"
        f"{cfg.top_k} d_expert={cfg.d_expert} vocab={cfg.vocab_size}, "
        f"{cfg.n_layers} of {full.n_layers} layers (the other "
        f"{full.n_layers - cfg.n_layers} would sit on further chips as "
        f"pipeline stages); kernels {resolved.kernels.describe()}")
    for line in resolved.describe().splitlines():
        print(f"    {line}")
    check(resolved.kernels == KernelPolicy.all_on(),
          f"kernels resolved to {resolved.kernels.describe()}, not all-on")

    # ---- phase 3: weights on the device ----
    t0 = time.perf_counter()
    llm = LLM.from_spec(resolved, cfg=cfg, dtype=jnp.bfloat16)
    leaves = jax.tree.leaves(llm.params)
    jax.block_until_ready(leaves)
    init_s = time.perf_counter() - t0
    wbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    check(dtypes == ["bfloat16"], f"weights hold {dtypes}, not bfloat16")
    mem = dev.memory_stats() or {}
    say("3 weights", f"{len(leaves)} arrays, {wbytes / 1e9:.2f} GB bf16 on "
        f"{dev.device_kind}; peak_bytes_in_use "
        f"{mem.get('peak_bytes_in_use', 'not reported')} of bytes_limit "
        f"{mem.get('bytes_limit', 'not reported')}; KV "
        f"{type(llm.engine.kv).__name__} of "
        f"{getattr(llm.engine.kv, 'pool_tokens', None)} tokens; init "
        f"{init_s:.1f} s")

    # ---- phase 4: serve through LLM.generate ----
    eng = llm.engine
    step_s: list = []
    plain_step = eng.step

    def timed_step(*a, **kw):
        t = time.perf_counter()
        out = plain_step(*a, **kw)
        step_s.append(time.perf_counter() - t)
        return out

    eng.step = timed_step
    ops.reset_counters()
    prompts = prompts_for(cfg, prompt_lens)
    t0 = time.perf_counter()
    outs = llm.generate(prompts, max_new)
    serve_s = time.perf_counter() - t0
    eng.step = plain_step
    ok = all(len(o) == max_new and all(0 <= t < cfg.vocab_size for t in o)
             for o in outs)
    check(ok, f"not every request finished: {[len(o) for o in outs]} tokens")
    say("4 serve", f"{len(prompts)} requests, prompts {list(prompt_lens)} "
        f"tokens, {max_new} new each, greedy: all finished, "
        f"{sum(map(len, outs))} tokens in {len(step_s)} steps")

    # ---- phase 5: the kernels are in the compiled step ----
    counters = {k: ops.counters.get(k, 0) for k in KERNELS}
    hlo = eng._unified.lower(*unified_args(eng)).compile().as_text()
    calls = kernel_calls_in(hlo)
    missing = [k for k, n in calls.items() if n == 0]
    check(not missing, f"no tpu_custom_call for {missing}")
    say("5 kernels", f"tpu_custom_call per kernel {calls}; trace counters "
        f"{counters}")
    zero = [k for k, n in counters.items() if n == 0]
    check(not zero, f"kernel counters at zero: {zero}")

    # ---- phase 6: kernels-on engine vs the float32 forward pass ----
    plan_off = dataclasses.replace(eng.plan, kernels=KernelPolicy.off())
    check_prompts = prompts[:CHECK_PROMPTS]
    # at the served depth, each engine against the bf16 forward: if the jnp
    # engine strays as far as the kernels-on one, depth is to blame, not a
    # kernel (printed, not gated; see the tolerance note at the top)
    deep = {}
    for kernels in ("on", "off"):
        llm_k = llm if kernels == "on" else LLM.from_spec(
            serve_spec(max_batch=len(prompt_lens),
                       prompt_len=max(prompt_lens), max_new_tokens=max_new,
                       kernels="off").resolve(cfg),
            cfg=cfg, params=llm.params)
        served = served_logits(llm_k, check_prompts, max_new)
        deep[kernels] = compare(served, reference_logits(
            llm.params, cfg, plan_off, served), cfg.vocab_size)
        del llm_k, served
    steady = sorted(step_s[1:]) or [float("nan")]
    times = (f"informational, one run: first step (compile + run) "
             f"{step_s[0]:.2f} s, steady step median "
             f"{steady[len(steady) // 2]:.4f} s over {len(steady)} steps, "
             f"generate wall {serve_s:.2f} s, unified-step compiles "
             f"{eng._unified._cache_size()}")
    cfg1 = dataclasses.replace(cfg, n_layers=GATE_LAYERS)
    params1 = first_layers(llm.params, GATE_LAYERS)
    del llm, eng, leaves, plain_step, timed_step, outs
    gc.collect()
    stats = {}
    for kernels in ("on", "off"):
        spec1 = serve_spec(max_batch=len(prompt_lens),
                           prompt_len=max(prompt_lens),
                           max_new_tokens=max_new, kernels=kernels)
        llm1 = LLM.from_spec(spec1.resolve(cfg1), cfg=cfg1, params=params1)
        served1 = served_logits(llm1, check_prompts, max_new)
        stats[kernels] = compare(
            served1, f32_reference(params1, cfg1, plan_off, served1),
            cfg.vocab_size)
        del llm1
        gc.collect()
    say("6 logits", f"teacher-forced vs the float32 forward, "
        f"{GATE_LAYERS} layer (the served weights' first): kernels-on "
        f"engine {describe_stats(stats['on'])}; kernels-off engine "
        f"{describe_stats(stats['off'])}; limit {SLACK_X}x + {SLACK_ABS:g}."
        f"  At the served {cfg.n_layers} layers vs the kernels-off bf16 "
        f"forward (printed, not gated): kernels-on engine "
        f"{describe_stats(deep['on'])}; kernels-off engine "
        f"{describe_stats(deep['off'])}")
    check(within(stats["on"], stats["off"]),
          "kernels-on logits less accurate than the jnp engine's")

    # ---- phase 7: times (informational, one run) ----
    say("7 times", times)


def first_layers(params, n: int):
    """The weights of a model cut to its first ``n`` layers."""
    import jax
    return {**params, "groups": [jax.tree.map(lambda x: x[:n], g)
                                 for g in params["groups"]]}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(devices, *, n_layers: int = FOUR_CHIP_LAYERS,
                   prompt_lens=PROMPT_LENS[:CHECK_PROMPTS],
                   max_new: int = MAX_NEW) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.policy import KernelPolicy
    from repro.launch.mesh import make_mesh
    from repro.models.model import init_params
    from repro.serving.api import LLM

    cfg = smoke_config(n_layers)
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    say("2 mesh", f"2x2 Auto mesh over {len(devices)} "
        f"{devices[0].device_kind}; {cfg.name} at published widths, "
        f"{cfg.n_layers} layers")

    with jax.default_device(devices[0]):
        params = init_params(jax.random.PRNGKey(SEED), cfg, jnp.bfloat16)
        jax.block_until_ready(params)
    spec = dict(max_batch=4, prompt_len=max(prompt_lens),
                max_new_tokens=max_new)
    ref_plan = dataclasses.replace(serve_spec(**spec).resolve(cfg).plan,
                                   kernels=KernelPolicy.off())
    prompts = prompts_for(cfg, prompt_lens)

    def error(llm) -> dict:
        served = served_logits(llm, prompts, max_new)
        with jax.default_device(devices[0]):
            ref = f32_reference(params, cfg, ref_plan, served)
        return compare(served, ref, cfg.vocab_size)

    base = error(LLM.from_spec(serve_spec(kernels="off", **spec), cfg=cfg,
                               params=params))
    gc.collect()
    say("3 reference", f"unsharded bf16 copy on {devices[0]} "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB); "
        f"its kernels-off engine vs the float32 forward: "
        f"{describe_stats(base)}")

    for strategy in ("mixserve", "pure_tp", "pure_ep"):
        t0 = time.perf_counter()
        llm = LLM.from_spec(serve_spec(strategy=strategy, **spec), cfg=cfg,
                            params=params, mesh=mesh)
        leaves = jax.tree.leaves(llm.params)
        spans = {len(x.sharding.device_set) for x in leaves}
        check(spans == {4}, f"{strategy}: weights span {spans} devices")
        split = sum(x.sharding.shard_shape(x.shape) != x.shape
                    for x in leaves)
        w_in = llm.params["groups"][0]["moe"]["w_in"]
        plan = llm.spec.plan
        stats = error(llm)
        say(f"4 {strategy}", f"plan tp={plan.tp} over {plan.tp_axes}, "
            f"ep={plan.ep} over {plan.ep_axes}, kernels "
            f"{llm.spec.kernels.describe()}; {len(leaves)} weights on 4 "
            f"devices, {split} split (w_in shard "
            f"{w_in.sharding.shard_shape(w_in.shape)} of {w_in.shape}); "
            f"{len(prompts)} requests served; vs the float32 forward "
            f"{describe_stats(stats)}, limit {SLACK_X}x the unsharded jnp "
            f"engine + {SLACK_ABS:g}; {time.perf_counter() - t0:.1f} s")
        check(within(stats, base), f"{strategy}: logits less accurate than "
              "the unsharded jnp engine's")
        del llm, leaves, w_in
        gc.collect()


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded phase and its unsharded "
                         "comparison")
    args = ap.parse_args(argv)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    say("1 platform", f"{dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {compile_cache.enable()}")
    try:
        if args.four_chips:
            run_four_chips(devices[:4])
        else:
            run_one_chip(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
