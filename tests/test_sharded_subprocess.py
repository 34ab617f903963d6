"""Sharded integration tests, each in a subprocess with its own fake-device
count (the main pytest process keeps the default 1 CPU device, per the
dry-run isolation rule)."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def _run(script: str, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(HERE, "sharded", script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if r.returncode != 0:
        pytest.fail(f"{script} failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}")
    return r.stdout


def test_moe_equivalence_across_plans():
    out = _run("run_moe_equivalence.py")
    assert "MOE_EQUIVALENCE_OK" in out


def test_sharded_model_matches_unsharded():
    out = _run("run_sharded_model.py")
    assert "SHARDED_MODEL_OK" in out


def test_overlap_exchange_bitwise_equivalence():
    """Micro-chunked count-bounded EP exchange == monolithic, bit for bit,
    incl. the overflow fallback under adversarial routing skew."""
    out = _run("run_overlap_equivalence.py")
    assert "OVERLAP_EQUIVALENCE_OK" in out


def test_trace_contract_census_matches_cost_model():
    """The traced moe_block's collective census == cost_model.comm_census
    for every strategy/algorithm/overlap, and a sabotaged block fails."""
    out = _run("run_trace_contract.py")
    assert "TRACE_CONTRACT_OK" in out


def test_engine_serves_on_mesh():
    """LLM.from_spec(mesh=...) shards the weights by the plan and serves
    the same greedy streams as the unsharded engine under every strategy."""
    out = _run("run_engine_mesh.py")
    assert "ENGINE_MESH_OK" in out
