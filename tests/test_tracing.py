"""The serving loop's profiler spans and step counters, and the expert
counts that stay on the device (docs/serving.md "Tracing").

A tiny MoE engine serves under ``jax.profiler`` on the CPU; the spans are
read back from the ``.xplane.pb`` the profiler wrote."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M
from _engine_helpers import make_engine
from repro.serving.engine import Request
from repro.serving.scheduler import Scheduler

KEY = jax.random.PRNGKey(0)
PHASES = ["engine.plan", "engine.reap", "engine.pack", "engine.upload",
          "engine.launch", "engine.readback", "engine.commit"]


@pytest.fixture(scope="module")
def moe():
    cfg = C.get_reduced("phi3.5-moe-42b")
    return cfg, M.init_params(KEY, cfg, jnp.float32)


def _requests():
    rng = np.random.default_rng(3)
    return [Request(rid=10 + i, prompt=rng.integers(
                0, 200, size=n).astype(np.int32),
                max_new_tokens=3, arrival=a)
            for i, (n, a) in enumerate([(13, 0.0), (5, 0.0), (7, 0.25)])]


def _spans(trace_dir):
    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[0])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats)) for ev in line.events
                       if ev.name.startswith(("engine.", "serve.")))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def served(moe, tmp_path_factory):
    """(engine, metrics, the q_lens each traced step fed, the spans, the
    q_lens of every step the engine ran)."""
    cfg, params = moe
    eng = make_engine(cfg, params, max_batch=2, max_len=64, chunk=8)
    every = []
    step = eng.unified_step

    def recording(q):
        every.append(np.asarray(q, np.int32).copy())
        return step(q)

    eng.unified_step = recording
    warm = Scheduler(eng)                # compile outside the trace
    warm.submit(Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                        max_new_tokens=2))
    warm.run()
    sched = Scheduler(eng)
    for r in _requests():
        sched.submit(r)
    d = str(tmp_path_factory.mktemp("trace"))
    n_warm = len(every)
    jax.profiler.start_trace(d)
    sched.run()
    jax.profiler.stop_trace()
    return eng, sched.metrics(), every[n_warm:], _spans(d), every


def test_each_step_holds_its_phases_in_order(served):
    eng, _, fed, spans, _ = served
    steps = [s for s in spans if s[0] == "engine.step"]
    launched = [s for s in steps if s[3]["rows"] > 0]
    assert len(launched) == sum(bool(q.any()) for q in fed) > 3
    for name, t0, t1, args in launched:
        kids = [s for s in spans if s[0] in PHASES and t0 <= s[1]
                and s[2] <= t1]
        assert [k[0] for k in kids] == PHASES
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        for k in kids:
            if k[0] in ("engine.launch", "engine.readback"):
                assert k[3] == {"step": args["step"]}
    assert [s[3]["step"] for s in steps] == sorted(s[3]["step"]
                                                   for s in steps)


def test_step_args_are_the_rows_it_fed(served):
    eng, _, fed, spans, _ = served
    steps = [s[3] for s in spans if s[0] == "engine.step"]
    assert len(steps) == len(fed)
    for args, q in zip(steps, fed):
        assert args["rows"] == int(q.sum())
        assert args["prefill_rows"] + args["decode_rows"] == args["rows"]
        assert args["buffer"] == eng.max_batch * eng.chunk == 16
        assert 0 <= args["slots"] <= eng.max_batch
    # a 13-token prompt prefills in chunks of 8: the first step is prefill
    assert steps[0]["prefill_rows"] == 8 + 5 and steps[0]["decode_rows"] == 0


def test_loop_spans(served):
    spans = served[3]
    names = [s[0] for s in spans]
    admits = sorted(s[3]["rid"] for s in spans if s[0] == "serve.admit")
    assert admits == [10, 11, 12]
    assert "serve.deadlines" in names
    assert "serve.wait" in names          # the third request comes later
    for name in ("serve.admit", "serve.wait", "serve.deadlines"):
        for _, a0, a1, _ in (s for s in spans if s[0] == name):
            assert not any(t0 <= a0 and a1 <= t1 for n, t0, t1, _ in spans
                           if n == "engine.step")


def test_counters_reach_the_metrics_row(served):
    eng, m, _, _, every = served
    # the counters hold every step the engine ran, warm-up included
    launched = [q for q in every if q.any()]
    rows = sum(int(q.sum()) for q in launched)
    want = rows / (len(launched) * eng.max_batch * eng.chunk)
    assert m.fed_row_share == pytest.approx(want, rel=1e-12)
    assert m.fed_row_share == eng.step_stats()["fed_row_share"]
    assert m.host_ms_per_step > 0 and m.readback_ms_max > 0
    assert "fed=" in m.row() and "readback_max=" in m.row()
    assert "fed_row_share" not in m.robustness()


def _stepped(moe, n, read_each, fold_every=None):
    cfg, params = moe
    eng = make_engine(cfg, params, max_batch=2, max_len=64, chunk=8)
    if fold_every:
        eng._ecnt_fold_every = fold_every
    for r in _requests()[:2]:
        r.max_new_tokens = n
        assert eng.admit(r)
    per_step = []
    for _ in range(n):
        eng.step()
        if read_each:
            per_step.append(eng.expert_counts)
    return eng, per_step


@pytest.mark.parametrize("fold_every", [None, 2])
def test_expert_counts_stay_on_device_until_read(moe, fold_every):
    cfg, _ = moe
    n = 5
    each, seen = _stepped(moe, n, read_each=True)
    once, _ = _stepped(moe, n, read_each=False, fold_every=fold_every)
    assert isinstance(once._ecnt, jax.Array)
    if fold_every is None:
        assert not once._ecnt_host.any()      # nothing read yet
    steps = np.diff(np.stack([np.zeros_like(seen[0])] + seen), axis=0)
    total = once.expert_counts                # the one read
    np.testing.assert_array_equal(total, steps.sum(axis=0))
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert (steps.sum(axis=1) == 2 * 8 * cfg.top_k * n_moe).all()
    assert total.dtype == np.int64
    np.testing.assert_array_equal(once.expert_counts, total)
