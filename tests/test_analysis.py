"""Tier-1 tests for the static-analysis subsystem (repro.analysis).

The linter's per-rule fixtures run through the real pipeline
(``lint_sources``), the shipped tree must stay clean, and the trace
contract's walker/census plumbing is exercised on a degenerate 1x1 mesh
(the multi-device census checks live in tests/sharded/run_trace_contract.py
behind the subprocess isolation rule)."""

import os
from pathlib import Path

import pytest

from repro.analysis.lint import (all_rules, lint_paths, lint_sources,
                                 self_test)

SRC = Path(__file__).resolve().parent.parent / "src"


# ---------------------------------------------------------------------------
# repro-lint: one positive + one negative fixture per rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", all_rules(), ids=lambda r: r.id)
def test_rule_catches_bad_fixture(rule):
    found = lint_sources({f"fixture_bad_{rule.id.lower()}": rule.FIXTURE_BAD},
                         rule_ids={rule.id})
    assert found, f"{rule.id} missed its seeded violation"
    assert all(f.rule == rule.id for f in found)


@pytest.mark.parametrize("rule", all_rules(), ids=lambda r: r.id)
def test_rule_passes_good_fixture(rule):
    found = lint_sources(
        {f"fixture_good_{rule.id.lower()}": rule.FIXTURE_GOOD},
        rule_ids={rule.id})
    assert not found, [str(f) for f in found]


def test_self_test_green():
    assert self_test() == 0


# the ROADMAP incident, verbatim shape: a stable argsort inside a
# shard_map body whose consumers include an interpret-mode pallas call —
# XLA:CPU duplicated the sort into both consumers and miscompiled one copy
_HISTORICAL_ARGSORT = '''
import functools
import jax
import jax.numpy as jnp
from jax import shard_map


def _moe_shard_fn(p, x, *, cfg, tp_axes, ep_axes):
    flat_e = x.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    return sorted_e


def moe_block(p, x, cfg, plan):
    fn = functools.partial(_moe_shard_fn, cfg=cfg, tp_axes=plan.tp_axes,
                           ep_axes=plan.ep_axes)
    return shard_map(fn, mesh=plan.mesh, in_specs=(None, None),
                     out_specs=None, check_vma=False)(p, x)
'''


def test_r1_historical_argsort_pattern():
    found = lint_sources({"histmod": _HISTORICAL_ARGSORT}, rule_ids={"R1"})
    assert len(found) == 1
    assert found[0].rule == "R1"
    assert "argsort" in found[0].message


def test_disable_comment_suppresses_and_no_disables_reveals():
    src = _HISTORICAL_ARGSORT.replace(
        "order = jnp.argsort(flat_e, stable=True)",
        "order = jnp.argsort(flat_e, stable=True)  # repro-lint: disable=R1")
    assert not lint_sources({"histmod": src}, rule_ids={"R1"})
    revealed = lint_sources({"histmod": src}, rule_ids={"R1"},
                            respect_disables=False)
    assert len(revealed) == 1


def test_r4_flags_missing_counter_and_oracle():
    from repro.analysis.rules.r4_kernel_contract import RULE as R4
    found = lint_sources({"kernels.ops2": R4.FIXTURE_BAD}, rule_ids={"R4"})
    msgs = " ".join(f.message for f in found)
    assert "counters" in msgs and "oracle" in msgs


def test_shipped_tree_is_clean():
    """Satellite 1, made permanent: `repro-lint src/` exits clean."""
    findings = lint_paths([SRC / "repro"])
    assert not findings, "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# comm_census structure (no mesh needed)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny-moe", family="moe", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=256, n_experts=8, top_k=2, d_expert=96,
                       n_shared_experts=1)


def test_census_layouts_and_counts():
    from repro.core.cost_model import Strategy, Workload, comm_census
    cfg, work = _tiny_cfg(), None
    from repro.core.cost_model import Workload
    work = Workload(batch=4, seq_len=8)

    hybrid = comm_census(cfg, Strategy(attn_tp=4, attn_dp=2, moe_tp=4,
                                       moe_ep=2, comm_algo="fused"), work)
    assert hybrid.layout == "mixserve" and hybrid.fused
    counts = hybrid.counts()   # traceable, unconditional
    assert counts[("all_to_all", "ep")] == 3     # counts + dispatch + combine
    assert counts[("all_gather", "tp")] == 2     # dispatch AG + epilogue AG
    assert counts[("reduce_scatter", "tp")] == 2  # combine RS + shared RS

    tp = comm_census(cfg, Strategy(attn_tp=8, attn_dp=1, moe_tp=8,
                                   moe_ep=1), work)
    assert tp.layout == "pure_tp"
    assert ("all_to_all", "ep") not in tp.counts()

    ep = comm_census(cfg, Strategy(attn_tp=4, attn_dp=2, moe_tp=1,
                                   moe_ep=8, comm_algo="fused"), work)
    assert ep.layout == "dp_ep" and ep.token_sliced and not ep.fused


def test_census_cap_bounded_adds_guard_and_conditional():
    from repro.core.cost_model import (EpOverlap, Strategy, Workload,
                                      comm_census)
    cfg = _tiny_cfg()
    strat = Strategy(attn_tp=4, attn_dp=2, moe_tp=4, moe_ep=2,
                     comm_algo="fused")
    work = Workload(batch=4, seq_len=8)
    census = comm_census(cfg, strat, work,
                         ep_overlap=EpOverlap(chunks=2, cap_rows=8),
                         tokens_local=16)
    assert census.cap_bounded and census.chunks == 2
    assert census.counts()[("all_reduce", "guard")] == 2   # pmax per chunk
    cond = census.counts(conditional=True)
    assert cond[("all_to_all", "ep")] == 4     # dispatch + combine, 2 chunks
    # monolithic census has neither
    mono = comm_census(cfg, strat, work)
    assert not mono.cap_bounded
    assert not mono.counts(conditional=True)
    assert ("all_reduce", "guard") not in mono.counts()


def test_census_priced_entries_feed_comm_latency():
    """comm_latency must price exactly the census's priced entries —
    the single-source-of-truth refactor (satellite 2)."""
    from repro.core.cost_model import (Strategy, Workload, comm_census,
                                      comm_latency)
    from repro.core.topology import H20_CLUSTER
    cfg = _tiny_cfg()
    work = Workload(batch=8, seq_len=16)
    s_fused = Strategy(attn_tp=4, attn_dp=2, moe_tp=4, moe_ep=2,
                       comm_algo="fused")
    s_unfused = Strategy(attn_tp=4, attn_dp=2, moe_tp=4, moe_ep=2,
                         comm_algo="unfused")
    t_fused = comm_latency(cfg, s_fused, work, H20_CLUSTER)
    t_unfused = comm_latency(cfg, s_unfused, work, H20_CLUSTER)
    assert 0 < t_fused < t_unfused   # the paper's fused < unfused ordering
    # every priced entry carries pricing inputs
    for census in (comm_census(cfg, s_fused, work),
                   comm_census(cfg, s_unfused, work)):
        for e in census.select(priced=True):
            assert e.bytes > 0 and e.degree > 1, e


# ---------------------------------------------------------------------------
# trace-contract plumbing on a 1x1 mesh (multi-device in tests/sharded/)
# ---------------------------------------------------------------------------

def test_jaxpr_census_walker_counts_and_cond():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.analysis.trace_contract import jaxpr_census
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))

    def body(x):
        y = jax.lax.psum(x, "model")
        y = jax.lax.all_to_all(y, "data", 0, 0, tiled=True)
        y = jax.lax.all_gather(y, "model", axis=0, tiled=True)
        y = jax.lax.psum_scatter(y, "model", scatter_dimension=0, tiled=True)

        def hot(z):
            return z

        def fallback(z):
            return jax.lax.psum(z, "data")

        return jax.lax.cond(y.sum() > 0, hot, fallback, y)

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data", "model"),
                      out_specs=P("data", "model"), check_vma=False)
    firm, cond = jaxpr_census(jax.make_jaxpr(f)(jnp.ones((2, 2))))
    m = frozenset({"model"})
    d = frozenset({"data"})
    assert firm[("all_reduce", m)] == 1
    assert firm[("all_to_all", d)] == 1
    assert firm[("all_gather", m)] == 1
    assert firm[("reduce_scatter", m)] == 1
    assert firm[("all_reduce", d)] == 0
    assert cond[("all_reduce", d)] == 1    # only on the fallback branch


def test_check_moe_census_degenerate_mesh():
    import jax

    from repro.analysis.trace_contract import check_moe_census
    from repro.core.partitioner import make_plan
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    plan = make_plan("mixserve", mesh, comm_algo="fused",
                     dispatch="dropless")
    r = check_moe_census(_tiny_cfg(), plan)
    assert r.ok, r


def test_purity_issues_detects_callbacks_and_dynamic_shapes():
    from repro.analysis.trace_contract import purity_issues
    clean = "func.func @main(%arg0: tensor<4x8xf32>) { stablehlo.add }"
    assert not purity_issues(clean)
    cb = ('stablehlo.custom_call @xla_python_cpu_callback(%arg0) '
          '{api_version = 2} : (tensor<4xf32>) -> tensor<4xf32>')
    dyn = "func.func @main(%arg0: tensor<?x8xf32>)"
    assert purity_issues(cb) and purity_issues(dyn)


def test_compile_watch_counts_compiles():
    import jax
    import jax.numpy as jnp

    from repro.analysis.compile_watch import CompileWatch

    def step_fn_probe(x):
        return x * 2.0

    f = jax.jit(step_fn_probe)
    with CompileWatch(match="step_fn_probe") as w:
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))            # cache hit: no new compile
    assert w.count == 1, w.events
    with CompileWatch(match="step_fn_probe") as w2:
        f(jnp.ones((8,)))            # new shape signature
    assert w2.count == 1


def test_check_retrace_reports():
    import jax
    import jax.numpy as jnp

    from repro.analysis.trace_contract import check_retrace

    f = jax.jit(lambda x: x + 1.0)
    r = check_retrace(lambda x: f(x),
                      [(jnp.ones((2,)),), (jnp.ones((3,)),)],
                      match="")
    assert r.ok
