"""The plain reference against the serving program's own float32 forward
pass at tiny sizes, the float8 control, and the entry point's refusals."""

import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness
from bench.reference import Reference, control_gaps, served_gaps, served_rows
from bench.tests._tiny import DATA
from bench.weights import Weights

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _samples(vocab, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, n), rng.integers(0, vocab, m))
            for n, m in ((9, 5), (30, 3), (4, 8))]


@pytest.mark.parametrize("conf", ["tiny-moe", "tiny-mla"])
def test_reference_matches_program_forward(conf):
    from repro.models.model import abstract_params, forward
    a = arch.arch_from(arch.load_config(DATA / f"{conf}.json"))
    cfg = arch.program_config(a)
    seed = 2 ** 32 + 17
    params = Weights(a, seed).tree_for(abstract_params(cfg, jnp.bfloat16))
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    samples = _samples(a.vocab)
    seqs, rows, _ = served_rows(samples)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(Reference(a, seed).logits(seqs, rows))
        got = []
        for s in seqs:
            lg = forward(p32, cfg, tokens=jnp.asarray(s)[None]).logits[0]
            got.append(np.asarray(lg))
    # the rows that predict each served token, in the program's own pass
    want = np.concatenate([lg[len(p) - 1:len(p) - 1 + len(out)]
                           for (p, out), lg in zip(samples, got)])
    assert np.abs(ref - want).max() <= 1e-3 * np.abs(want).max()


def test_served_gaps_are_zero_for_the_references_own_tokens():
    a = arch.arch_from(arch.load_config(DATA / "tiny-moe.json"))
    prompt = np.arange(5, 17) % a.vocab
    seqs, rows, _ = served_rows([(prompt, [0])])
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(Reference(a, 9).logits(seqs, rows))
    greedy = int(lg[0].argmax())
    gaps, margins = served_gaps(a, 9, [(prompt, [greedy])])
    assert gaps.tolist() == [0.0] and margins.shape == (1,)
    gaps, _ = served_gaps(a, 9, [(prompt, [(greedy + 1) % a.vocab])])
    assert gaps[0] > 0


@pytest.mark.parametrize("conf", ["tiny-moe", "tiny-mla"])
def test_float8_control_fails_the_limit(conf):
    """The control (the reference with float8 inputs to every matrix
    product) fails the configuration's limit on every seed tried."""
    a = arch.arch_from(arch.load_config(DATA / f"{conf}.json"))
    limits = arch.load_config(DATA / f"{conf}.json")["correct"]
    rng = np.random.default_rng(0)
    for seed in (21, 22, 23):
        samples = [(rng.integers(0, a.vocab, 24), rng.integers(0, a.vocab, 16))
                   for _ in range(8)]
        stats = harness.gap_stats(control_gaps(a, seed, samples))
        ok, compared = harness.judge(stats, 0, limits)
        assert not ok, compared


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi35-l4.chat",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_chip():
    p = _run_py(ROOT, {})
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
