"""Trace contract: the cost model priced the program XLA actually runs.

``cost_model.comm_census`` claims a per-layer collective list for each
strategy; this module traces the real lowered program on a CPU mesh and
demands the two agree — the MixServe analyzer's accounting, made
falsifiable (docs/analysis.md).  Three checks:

(a) **Collective census** — the shard_map jaxpr of
    ``models.moe.moe_block`` must contain exactly the census's
    ``traceable`` collectives: same kinds, same mesh-axis groups, same
    counts.  Collectives inside ``lax.cond`` (the count-bounded
    exchange's worst-case fallback) are matched against the census's
    ``conditional`` entries.  An extra all-reduce slipped into the block
    — or a census entry the implementation stopped emitting — fails.
(b) **Retrace detector** — across a declared set of shape signatures the
    step compiles exactly ONCE per signature (``jax.log_compiles`` via
    ``CompileWatch``); a silent retrace otherwise only shows up as a
    production latency cliff.
(c) **Purity** — the lowered StableHLO contains no host callbacks
    (``io_callback``/``pure_callback``/debug prints smuggle a host sync
    into the hot path) and no dynamic shapes.

CLI::

    python -m repro.analysis.trace_contract                 # tiny sweep
    python -m repro.analysis.trace_contract --spec qwen3-235b-a22b \\
        --strategies mixserve --mesh 2x4 --json report.json

jax is imported inside functions only, so the CLI can set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
import (the same subprocess idiom as ``tests/sharded/``).
"""

from __future__ import annotations

import dataclasses
import json
from collections import Counter
from typing import Optional

# jaxpr collective primitive -> census kind (note
# lax.psum_scatter lowers to the primitive named "reduce_scatter")
_PRIM_KIND = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_to_all": "all_to_all",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
}
# census logical axis -> mesh axis-name group (resolved per plan below)
_CALLBACK_MARKERS = ("callback", "CustomCall(\"xla_python",
                     "xla_python_cpu_callback", "xla_ffi_python")


def _axes_of(eqn) -> frozenset:
    p = eqn.params
    ax = p.get("axes", p.get("axis_name", ()))
    if isinstance(ax, str):
        ax = (ax,)
    return frozenset(ax)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for vv in vs:
            if hasattr(vv, "eqns"):            # plain Jaxpr (shard_map)
                yield vv
            elif hasattr(vv, "jaxpr"):         # ClosedJaxpr (pjit, scan)
                yield vv.jaxpr


def _walk(jaxpr, firm: Counter, cond: Counter, mult: int = 1) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        kind = _PRIM_KIND.get(name)
        if kind is not None:
            firm[(kind, _axes_of(eqn))] += mult
            continue
        if name == "cond":
            per = []
            for br in eqn.params["branches"]:
                f, c = Counter(), Counter()
                _walk(br.jaxpr if hasattr(br, "jaxpr") else br, f, c)
                per.append((f, c))
            keys = set()
            for f, c in per:
                keys |= set(f) | set(c)
            for k in keys:
                lo = min(f[k] for f, _ in per)
                hi = max(f[k] + c[k] for f, c in per)
                firm[k] += lo * mult
                cond[k] += (hi - lo) * mult
            continue
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            f, c = Counter(), Counter()
            _walk(eqn.params["jaxpr"].jaxpr, f, c)
            for k, v in f.items():
                firm[k] += v * length * mult
            for k, v in c.items():
                cond[k] += v * length * mult
            continue
        if name == "while":
            # data-dependent trip count: any collective inside is counted
            # as conditional (the census has no unconditional claim on it)
            f, c = Counter(), Counter()
            for sub in _sub_jaxprs(eqn):
                _walk(sub, f, c)
            for k, v in (f + c).items():
                cond[k] += v * mult
            continue
        for sub in _sub_jaxprs(eqn):
            _walk(sub, firm, cond, mult)


def jaxpr_census(closed_jaxpr) -> tuple[Counter, Counter]:
    """(firm, conditional) Counters keyed (kind, frozenset(mesh axes)).

    ``firm`` counts collectives on every execution; ``conditional`` the
    extra issues the worst-case ``lax.cond``/``while`` paths may add.
    """
    firm: Counter = Counter()
    cond: Counter = Counter()
    _walk(closed_jaxpr.jaxpr, firm, cond)
    return firm, cond


# ---------------------------------------------------------------------------
# Expected counters from the cost model census
# ---------------------------------------------------------------------------

def strategy_for_plan(plan):
    """The cost-model ``Strategy`` a ShardingPlan realizes (inverse of
    ``partitioner.make_plan``'s canonical layouts, pod-less meshes)."""
    from repro.core.cost_model import Strategy
    tp = plan.axis_size(plan.tp_axes)
    ep = plan.axis_size(plan.ep_axes)
    n = plan.mesh.devices.size if plan.mesh is not None else 1
    token_sliced = bool(set(plan.tp_axes) & set(plan.ep_axes))
    if ep <= 1:                                   # pure_tp
        return Strategy(attn_tp=tp, attn_dp=max(1, n // tp),
                        moe_tp=tp, moe_ep=1, comm_algo=plan.comm_algo)
    if token_sliced:                              # dp_ep (pure EP)
        return Strategy(attn_tp=tp, attn_dp=max(1, n // tp),
                        moe_tp=1, moe_ep=ep, comm_algo="unfused")
    return Strategy(attn_tp=tp, attn_dp=max(1, n // tp),   # mixserve
                    moe_tp=tp, moe_ep=ep, comm_algo=plan.comm_algo)


def tokens_local_for(plan, batch: int, seq: int) -> int:
    """Per-rank token count the shard body sees — pins the census's
    micro-chunk count C and cap decision to the traced program's."""
    r = plan.rules.get("batch") or ()
    r = r if isinstance(r, tuple) else (r,)
    b_local = max(1, batch // max(1, plan.axis_size(tuple(a for a in r if a))))
    t = b_local * seq
    if set(plan.tp_axes) & set(plan.ep_axes):     # token-sliced body
        tp = plan.axis_size(plan.tp_axes)
        t = (t + (-t) % tp) // tp                 # pad-to-tp then slice
    return max(1, t)


def expected_census(census, plan) -> tuple[Counter, Counter]:
    """Resolve the census's logical axes onto the plan's mesh axis names;
    returns (firm, conditional) Counters shaped like ``jaxpr_census``."""
    groups = {
        "tp": frozenset(plan.tp_axes),
        "ep": frozenset(plan.ep_axes),
        "guard": frozenset(plan.tp_axes) | frozenset(plan.ep_axes),
        "mesh": frozenset(plan.mesh.axis_names) if plan.mesh is not None
        else frozenset(),
    }
    firm: Counter = Counter()
    cond: Counter = Counter()
    for e in census.entries:
        if not e.traceable:
            continue
        key = (e.kind, groups[e.axis])
        (cond if e.conditional else firm)[key] += e.count
    return firm, cond


# ---------------------------------------------------------------------------
# Check (a): the MoE block's collective census
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ContractReport:
    name: str
    ok: bool
    expected: dict
    actual: dict
    mismatches: list

    def as_json(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        head = f"[{'OK' if self.ok else 'FAIL'}] {self.name}"
        if self.ok:
            return head
        return head + "".join(f"\n  {m}" for m in self.mismatches)


def _fmt(key) -> str:
    kind, axes = key
    return f"{kind}({','.join(sorted(axes))})"


def _diff(name: str, expected: Counter, actual: Counter) -> list:
    out = []
    for k in sorted(set(expected) | set(actual), key=_fmt):
        e, a = expected.get(k, 0), actual.get(k, 0)
        if e != a:
            out.append(f"{name}: {_fmt(k)} expected {e}, traced {a}")
    return out


def check_moe_census(cfg, plan, *, batch: int = 4, seq: int = 8,
                     name: str = "moe_census") -> ContractReport:
    """Check (a): trace ``moe_block`` on the plan's mesh abstractly and
    compare its collective census against ``cost_model.comm_census``."""
    import jax
    import jax.numpy as jnp

    from repro.core.cost_model import Workload, comm_census
    from repro.models import moe as M
    from repro.models.param import init_tree

    strat = strategy_for_plan(plan)
    work = Workload(batch=batch, seq_len=seq)
    census = comm_census(cfg, strat, work, ep_overlap=plan.ep_overlap,
                         tokens_local=tokens_local_for(plan, batch, seq))
    exp_firm, exp_cond = expected_census(census, plan)

    # abstract trace: ShapeDtypeStructs cost no device memory, so the
    # contract runs on paper-size configs too
    p_shapes = jax.eval_shape(
        lambda k: init_tree(k, M.moe_spec(cfg), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    x_shape = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, xx: M.moe_block(p, xx, cfg, plan))(p_shapes, x_shape)
    act_firm, act_cond = jaxpr_census(jaxpr)

    mismatches = _diff("firm", exp_firm, act_firm) \
        + _diff("conditional", exp_cond, act_cond)
    to_d = lambda c: {_fmt(k): v for k, v in sorted(c.items(), key=lambda i:
                                                    _fmt(i[0]))}
    return ContractReport(
        name=name, ok=not mismatches,
        expected={"firm": to_d(exp_firm), "conditional": to_d(exp_cond)},
        actual={"firm": to_d(act_firm), "conditional": to_d(act_cond)},
        mismatches=mismatches)


# ---------------------------------------------------------------------------
# Check (b): retrace detector
# ---------------------------------------------------------------------------

def check_retrace(fn, arg_sets, *, match: str = "",
                  name: str = "retrace") -> ContractReport:
    """Check (b): running ``fn`` over ``arg_sets`` (one per declared shape
    signature, each repeated twice) must compile exactly once per set."""
    from repro.analysis.compile_watch import CompileWatch

    with CompileWatch(match=match) as w:
        for args in arg_sets:
            fn(*args)
            fn(*args)           # second call must hit the cache
    expected, actual = len(arg_sets), w.count
    ok = actual <= expected     # dedup'd signatures may share a compile
    return ContractReport(
        name=name, ok=ok, expected={"compiles": expected},
        actual={"compiles": actual},
        mismatches=[] if ok else [
            f"{actual} compiles for {expected} shape signatures — "
            "a shape/dtype/static-arg leak is retracing the step"])


# ---------------------------------------------------------------------------
# Check (c): purity of the lowered module
# ---------------------------------------------------------------------------

def purity_issues(stablehlo_text: str) -> list:
    issues = []
    for line in stablehlo_text.splitlines():
        if "custom_call" in line and any(m in line for m in
                                         _CALLBACK_MARKERS):
            issues.append(f"host callback in lowered module: {line.strip()[:120]}")
        if "tensor<?x" in line or "x?x" in line:
            issues.append(f"dynamic shape in lowered module: {line.strip()[:120]}")
    return issues


def check_purity(lowered_text: str, *, name: str = "purity") -> ContractReport:
    issues = purity_issues(lowered_text)
    return ContractReport(name=name, ok=not issues, expected={},
                          actual={}, mismatches=issues)


def check_moe_purity(cfg, plan, *, batch: int = 4, seq: int = 8,
                     name: str = "moe_purity") -> ContractReport:
    import jax
    import jax.numpy as jnp

    from repro.models import moe as M
    from repro.models.param import init_tree

    p_shapes = jax.eval_shape(
        lambda k: init_tree(k, M.moe_spec(cfg), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    x_shape = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)
    text = jax.jit(lambda p, xx: M.moe_block(p, xx, cfg, plan)).lower(
        p_shapes, x_shape).as_text()
    return check_purity(text, name=name)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny-moe", family="moe", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=256, n_experts=8, top_k=2, d_expert=96,
                       n_shared_experts=1)


def run_contract(arch: Optional[str], mesh_shape: tuple, strategies,
                 comm_algo: str, chunks: int, purity: bool,
                 cap_rows: int = -1) -> list:
    """Build the mesh, resolve each strategy to a plan, run the checks."""
    import jax

    from repro.core.cost_model import EpOverlap
    from repro.core.partitioner import make_plan
    from repro.launch.mesh import make_mesh

    ep_overlap = EpOverlap(chunks=max(1, chunks), cap_rows=cap_rows) \
        if chunks > 1 or cap_rows >= 0 else None
    mesh = make_mesh(mesh_shape, ("data", "model"))

    if arch is None:
        cfg = _tiny_cfg()
    else:
        from repro.serving.api import ServeSpec
        import repro.configs as C
        spec = ServeSpec(arch=arch)
        resolved = spec.resolve(mesh=mesh)
        cfg = C.get(arch)
        if not strategies:
            strategies = [resolved.strategy]

    reports = []
    for strat in strategies or ["mixserve", "pure_tp", "dp_ep"]:
        plan = make_plan(strat, mesh, comm_algo=comm_algo,
                         dispatch="dropless", ep_overlap=ep_overlap)
        tag = f"{cfg.name}/{strat}/{comm_algo}" \
              + (f"/chunks={chunks}" if chunks > 1 else "")
        reports.append(check_moe_census(cfg, plan,
                                        name=f"moe_census[{tag}]"))
        if purity:
            reports.append(check_moe_purity(cfg, plan,
                                            name=f"moe_purity[{tag}]"))
    return reports


def main(argv=None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.trace_contract",
        description="cross-check cost_model.comm_census against the "
                    "lowered MoE program on a CPU mesh")
    ap.add_argument("--spec", default=None, metavar="ARCH",
                    help="registry arch id; resolves a ServeSpec for the "
                         "mesh (default: the tiny 8-expert test config)")
    ap.add_argument("--mesh", default="2x4",
                    help="data x model CPU mesh, e.g. 2x4 (default)")
    ap.add_argument("--strategies", default="",
                    help="comma list of mixserve,pure_tp,dp_ep "
                         "(default: all three; with --spec: the resolved one)")
    ap.add_argument("--algo", default="fused",
                    choices=["fused", "sync", "unfused"])
    ap.add_argument("--chunks", type=int, default=0,
                    help="micro-chunked EP overlap chunk count (0 = off)")
    ap.add_argument("--cap-rows", type=int, default=-1,
                    help="count-bound row cap (-1 = worst case, 0 = auto "
                         "rule, >0 explicit — small values exercise the "
                         "conditional overflow fallback)")
    ap.add_argument("--no-purity", action="store_true",
                    help="skip the lowered-StableHLO purity scan")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the reports as JSON")
    args = ap.parse_args(argv)

    shape = tuple(int(d) for d in args.mesh.lower().split("x"))
    if len(shape) != 2:
        ap.error("--mesh must be RxC (two axes: data x model)")
    n = shape[0] * shape[1]
    # must precede the first jax import (the tests/sharded idiom)
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={n}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    strategies = [s for s in args.strategies.split(",") if s]
    reports = run_contract(args.spec, shape, strategies, args.algo,
                           args.chunks, purity=not args.no_purity,
                           cap_rows=args.cap_rows)
    for r in reports:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump([r.as_json() for r in reports], f, indent=2)
    return 0 if all(r.ok for r in reports) else 1


if __name__ == "__main__":
    raise SystemExit(main())


__all__ = ["jaxpr_census", "expected_census", "strategy_for_plan",
           "tokens_local_for", "check_moe_census", "check_retrace",
           "purity_issues", "check_purity", "check_moe_purity",
           "ContractReport", "run_contract"]
