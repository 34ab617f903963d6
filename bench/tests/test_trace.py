"""The reduction from a profiler trace to per-layer numbers, on a small
trace recorded on a v5e chip: phi3.5-moe-42b-l4 serving two requests
(3 unified steps), with the harness's host spans."""

import gzip
import json
import pathlib
import shutil

import numpy as np
import pytest

from bench import harness, readers
from bench import trace as tr
from bench.peaks import peaks_for

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(DATA / "trace-phi35-l4.xplane.pb.gz") as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(str(d.parents[2]))


def test_events_of_the_chip_and_the_host(recorded):
    steps = json.loads((DATA / "trace-phi35-l4.steps.json").read_text())
    assert len(tr.program_steps(recorded)) == len(steps) == 3
    assert {s.name for s in recorded.spans} == {"bench.step", "bench.admit"}
    assert sum(s.name == "bench.step" for s in recorded.spans) == 3
    assert all(e.dur >= 0 for e in recorded.ops)


def test_busy_within_window(recorded):
    w0, w1 = tr.window(recorded)
    busy = tr.busy_s(recorded)
    assert 0 < busy <= w1 - w0
    merged = tr.union(recorded.ops)
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))


def test_kernels_are_found_by_name(recorded):
    n = len(tr.program_steps(recorded))
    secs, calls = tr.kernel_s(recorded, "grouped_gemm")
    assert calls == 3 * 4 * n and secs > 0          # up, gate, down x 4
    secs, calls = tr.kernel_s(recorded, "flash_chunk_paged")
    assert calls == 4 * n and secs > 0
    assert tr.kernel_s(recorded, "flash_chunk") == (0, 0)
    assert tr.op_kind("flash_chunk_paged.10") == "flash_chunk_paged"
    assert tr.op_kind("while.64") == "while"


def test_breakdown_lists(recorded):
    top = tr.top_ops(recorded)
    assert len(top) == 10 and all(tr.op_kind(k) != "while" for k, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
    gaps = tr.idle_gaps(recorded)
    assert gaps and all(
        g[0].split(" > ")[0] in ("bench.step", "bench.admit", "host.loop")
        for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_idle_gap_names_the_host_event_inside_it():
    ev = tr.Event
    trace = tr.Trace(
        ops=[ev("a", 0.0, 1.0), ev("b", 3.0, 1.0), ev("c", 4.5, 0.5)],
        modules=[], spans=[ev("bench.step", 0.5, 3.0)],
        host=[ev("tpu::System::Execute", 0.0, 9.0),
              ev("np.asarray(jax.Array)", 1.2, 1.8)])
    assert tr.idle_gaps(trace) == [
        ["bench.step > np.asarray(jax.Array)", 2.0],
        ["host.loop > tpu::System::Execute", 0.5]]


def test_readers_on_the_recorded_trace(recorded):
    from bench import arch
    steps = json.loads((DATA / "trace-phi35-l4.steps.json").read_text())
    a = arch.arch_from(arch.load_config(
        harness.ROOT / "bench/configs/phi3.5-moe-42b-l4.json"))

    class Spec:
        max_batch, chunk = 8, 64

    class Rec:
        pass
    rec = Rec()
    rec.steps = [harness.Step(t0, t1, np.asarray(q), np.asarray(o))
                 for t0, t1, q, o in steps]
    run = harness.Run(arch=a, spec=Spec(),
                      peaks=peaks_for("TPU v5 lite"), rec=rec, requests=[],
                      t_open=steps[0][0], t_close=steps[-1][1],
                      trace=recorded)
    idle = readers.idle_share(run)
    assert 0 < idle < 100
    assert readers.step_ms(run) > 0
    for share in (readers.grouped_gemm_roofline(run),
                  readers.attention_roofline(run, "flash_chunk_paged"),
                  readers.step_mfu(run)):
        assert 0 < share <= 100
    assert readers.attention_roofline(run, "flash_chunk") is None
    run.trace = None
    assert readers.idle_share(run) is None and readers.step_ms(run) is None
