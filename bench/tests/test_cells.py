"""The harness end to end on the CPU at tiny sizes: a cell added as files
alone is found by name and runs, and the comparison that decides
``correct`` passes a sound run and fails each fault the timed path can
have on one chip."""

import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests._tiny import tiny_root


def run(tmp_path, cell, breaker=None, trace=False, seed=2 ** 31 + 11):
    root, bench = tiny_root(tmp_path)
    return harness.run_cell(cell, seed, 1.5, trace, t_start=time.perf_counter(),
                            root=root, require_chip=False, bench=bench,
                            breaker=breaker)


@pytest.mark.parametrize("cell", ["moe.open", "moe.closed", "mla.open"])
def test_sound_run_is_correct(tmp_path, cell):
    result, lines = run(tmp_path, cell)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert "setup_s" in result["metrics"]
    assert lines[-1].startswith("compared: ")


def test_traced_run_reports_layer_metrics(tmp_path):
    result, lines = run(tmp_path, "moe.open", trace=True)
    assert result["correct"], lines
    assert "queue_wait_mean_ms.chat" in result["metrics"]
    assert "window_s" in result["device"] and "busy_s" in result["device"]
    assert "ttft_mean_ms" not in result["metrics"]


def _stale_state(eng):
    """The step returns the KV state it was given, unchanged."""
    step = eng._unified

    def broken(params, toks, q, v, cache, key):
        out = step(params, toks, q, v, cache, key)
        return out[:4] + (cache,) + out[5:]
    eng._unified = broken


def _half_batch(eng):
    """Every other slot's rows never reach the model."""
    step = eng._unified

    def broken(params, toks, q, v, cache, key):
        return step(params, toks.at[1::2].set(0), q, v, cache, key)
    eng._unified = broken


def _altered_token(eng):
    """Slot 0's sampled token is changed where the step produces it."""
    step = eng._unified
    vocab = eng.cfg.vocab_size

    def broken(params, toks, q, v, cache, key):
        out = step(params, toks, q, v, cache, key)
        nxt = out[0].at[0].set((out[0][0] + 1) % vocab)
        return (nxt,) + out[1:]
    eng._unified = broken


@pytest.mark.parametrize("breaker", [_stale_state, _half_batch,
                                     _altered_token],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
@pytest.mark.parametrize("cell", ["moe.open", "mla.open"])
def test_fault_makes_run_incorrect(tmp_path, cell, breaker):
    result, lines = run(tmp_path, cell, breaker=breaker)
    assert not result["correct"], lines


def _swapped_router(monkeypatch, near_ties_only: bool):
    """The program's router sends each token to its (k+1)-th expert in
    place of its k-th: on rows whose two router logits lie within
    ``harness.TIE_MARGIN`` of each other, or on every row."""
    import jax

    from repro.models import moe
    route = moe.route_topk

    def swapped(logits, k, renorm=True, use_kernel=False):
        lg = logits.astype(jnp.float32)
        top_l, top_i = jax.lax.top_k(lg, k + 1)
        swap = top_l[:, k - 1] - top_l[:, k] < harness.TIE_MARGIN
        if not near_ties_only:
            swap = jnp.ones_like(swap)
        kth = jax.nn.one_hot(top_i[:, k - 1], lg.shape[-1], dtype=bool)
        lg = jnp.where(swap[:, None] & kth, -jnp.inf, lg)
        return route(lg.astype(logits.dtype), k, renorm, use_kernel)
    monkeypatch.setattr(moe, "route_topk", swapped)


@pytest.mark.parametrize("near_ties_only", [True, False],
                         ids=["near_ties", "every_row"])
@pytest.mark.parametrize("cell", ["moe.open", "mla.open"])
def test_router_swapping_kth_expert(tmp_path, monkeypatch, cell,
                                    near_ties_only):
    """A swap on every row is caught.  A swap only where the k-th and
    (k+1)-th router logits lie within TIE_MARGIN is not: those rows are
    the near-ties the comparison leaves out, where bf16 rounding may pick
    either expert."""
    _swapped_router(monkeypatch, near_ties_only)
    result, lines = run(tmp_path, cell)
    assert result["correct"] is near_ties_only, lines
    ref = next(line for line in lines if line.startswith("reference: "))
    assert float(ref.split("tie_share ")[1].split(",")[0]) > 0, ref


def test_unknown_cell_is_refused(tmp_path):
    with pytest.raises(harness.NotRunnable):
        run(tmp_path, "no.such.cell")


def test_weights_are_bf16_and_match_slices():
    from bench import arch
    from bench.tests._tiny import DATA
    from bench.weights import Weights
    from repro.models.model import abstract_params
    a = arch.arch_from(arch.load_config(DATA / "tiny-mla.json"))
    w = Weights(a, 2 ** 33 + 5)
    tree = w.tree_for(abstract_params(arch.program_config(a), jnp.bfloat16))
    moe = tree["groups"][1]["moe"]
    assert moe["w_in"].dtype == jnp.bfloat16
    blk = w.block("groups/1/moe/w_in", (0, 2), 3)
    assert bool((blk == moe["w_in"][0, 2:5]).all())
    assert bool((w.block("embed", ()) == tree["embed"]).all())
    assert float(jnp.std(tree["embed"].astype(jnp.float32))) == \
        pytest.approx(1.0, rel=0.05)
    other = Weights(a, 2 ** 33 + 6).block("embed", ())
    assert not bool((other == tree["embed"]).all())
