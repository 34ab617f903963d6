"""The device's idle time cut by the serving program's own spans
(``bench/spans.py``): the arithmetic on hand-made events, and the readings
on two traces recorded on a v5e chip, one from before the program had
spans and one with them."""

import gzip
import pathlib
import shutil

import pytest

from bench import spans as sp
from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
ev = tr.Event


def S(name, start, dur, **args):
    return sp.Span(name, start, dur, args)


def _trace(ops, n_steps=1):
    return tr.Trace(ops=ops, spans=[], host=[],
                    modules=[ev("jit__unified_impl", ops[0].start, 0.1)]
                    * n_steps)


def test_nested_spans_take_the_innermost():
    # busy [0, 1) and [9, 10): idle [1, 9)
    trace = _trace([ev("a", 0.0, 1.0), ev("b", 9.0, 1.0)], n_steps=2)
    program = [S("engine.step", 0.5, 9.0, rows=3, buffer=16),
               S("engine.pack", 1.5, 1.0),
               S("engine.readback", 4.0, 4.0),
               S("engine.readback", 4.5, 0.0)]          # empty: ignored
    by = sp.idle_by_span(trace, program)
    assert by == pytest.approx({"engine.step": 3.0, "engine.pack": 1.0,
                                "engine.readback": 4.0, "": 0.0})
    assert sp.idle_readback_ms(trace, program) == pytest.approx(2000.0)
    assert sp.idle_host_ms(trace, program) == pytest.approx(2000.0)


def test_gaps_no_span_covers_and_the_wait():
    # window [0, 20): busy [0, 2), [10, 12), [19, 20)
    trace = _trace([ev("a", 0.0, 2.0), ev("b", 10.0, 2.0),
                    ev("c", 19.0, 1.0)])
    program = [S("serve.wait", 3.0, 2.0), S("serve.wait", 5.0, 2.0),
               S("serve.admit", 7.5, 0.5, rid=4),
               S("serve.deadlines", 12.5, 0.5),
               S("engine.step", 14.0, 5.5, rows=1, buffer=8),
               S("engine.readback", 15.0, 4.0)]
    by = sp.idle_by_span(trace, program)
    assert by == pytest.approx({"serve.wait": 4.0, "serve.admit": 0.5,
                                "serve.deadlines": 0.5, "engine.step": 1.0,
                                "engine.readback": 4.0, "": 3.5 + 1.5})
    idle = sum(e - s for s, e in sp.idle_intervals(trace))
    assert sum(by.values()) == pytest.approx(idle) == pytest.approx(15.0)
    # the wait and the uncovered time are not host work
    assert sp.idle_host_ms(trace, program) == pytest.approx(2000.0)
    assert sp.fed_row_share(trace, program) == pytest.approx(12.5)


def test_spans_past_the_window_are_cut_to_it():
    trace = _trace([ev("a", 1.0, 1.0), ev("b", 3.0, 1.0)])
    program = [S("serve.wait", 0.0, 1.5), S("engine.step", 1.5, 10.0,
                                            rows=2, buffer=4)]
    by = sp.idle_by_span(trace, program)
    assert by == pytest.approx({"engine.step": 1.0, "": 0.0})
    assert sp.innermost(program, 1.0, 4.0) == [(1.0, 1.5, "serve.wait"),
                                              (1.5, 4.0, "engine.step")]


def test_no_program_spans_read_nothing():
    trace = _trace([ev("a", 0.0, 1.0), ev("b", 2.0, 1.0)])
    assert sp.idle_host_ms(trace, []) is None
    assert sp.idle_readback_ms(trace, []) is None
    assert sp.fed_row_share(trace, []) is None
    assert sp.idle_by_span(trace, []) == pytest.approx({"": 1.0})


def _unpack(tmp_path, name):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(DATA / name) as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def test_the_older_recording_has_no_program_spans(tmp_path):
    d = _unpack(tmp_path, "trace-phi35-l4.xplane.pb.gz")
    trace, program = tr.load(d), sp.load_program(d)
    assert program == []
    assert {s.name for s in trace.spans} == {"bench.step", "bench.admit"}
    assert sp.idle_host_ms(trace, program) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = _unpack(tmp_path_factory.mktemp("program"),
                "trace-phi35-l4-program.xplane.pb.gz")
    return tr.load(d), sp.load_program(d)


def test_recording_reads_all_three(recorded):
    trace, program = recorded
    n = len(tr.program_steps(trace))
    assert 3 <= n <= 5
    steps = [s for s in program if s.name == "engine.step"]
    assert sum(s.args["rows"] > 0 for s in steps) == n
    assert all(s.args["buffer"] == 8 * 64 for s in steps)
    assert {s.args["rid"] for s in program if s.name == "serve.admit"} \
        and all(s.args["step"] == st.args["step"] for st in steps
                for s in program if s.name in ("engine.launch",
                                               "engine.readback")
                and st.start <= s.start <= st.start + st.dur)
    host = sp.idle_host_ms(trace, program)
    readback = sp.idle_readback_ms(trace, program)
    share = sp.fed_row_share(trace, program)
    assert host > 0 and readback >= 0 and 0 < share <= 100


def test_recording_idle_parts_sum_to_the_idle_time(recorded):
    trace, program = recorded
    by = sp.idle_by_span(trace, program)
    w0, w1 = tr.window(trace)
    idle = (w1 - w0) - tr.busy_s(trace)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-9)
    assert all(v >= -1e-12 for v in by.values())
    split = sp.split(trace, program)
    assert split["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert set(split["host_ms_by_span"]) >= {
        "engine.step", "engine.plan", "engine.pack", "engine.upload",
        "engine.launch", "engine.readback", "engine.commit"}


def test_traced_run_of_a_tiny_cell(tmp_path):
    """The command line's traced run, on the CPU at a tiny size: the
    split comes from the same trace as the run's own result line."""
    import time

    from bench.tests._tiny import tiny_root
    root, bench = tiny_root(tmp_path)
    bench["per_layer"].append({"name": "fed_row_share.chat", "unit": "%",
                               "workloads": ["moe.open", "mla.open"]})
    result, _, split = sp._traced_run(
        "moe.open", 2 ** 31 + 5, 1.5, time.perf_counter(), root=root,
        require_chip=False, bench=bench)
    assert result["correct"]
    dev = result["device"]
    # the CPU has no device plane: no ops, no step programs, all idle
    assert split["window_s"] == pytest.approx(dev["window_s"])
    assert split["idle_s"] == pytest.approx(dev["window_s"] - dev["busy_s"])
    assert split["fed_row_share"] > 0
    assert 0 <= split["uncovered_share"] < 1
    assert {"engine.step", "engine.readback", "serve.admit"} \
        <= set(split["host_ms_by_span"])
    # the engine's own counter, over every step it ran (warm-up included)
    assert 0 < result["metrics"]["fed_row_share.chat"]["value"] <= 100


def test_fed_row_share_reads_nothing_without_the_counter():
    from bench import harness

    class Rec:
        engine = object()          # an engine that keeps no step counters
    read = harness.metric_reader("fed_row_share.chat")
    assert read(harness.Run(None, None, None, Rec(), [], 0.0, 1.0)) is None


def test_record_keeps_a_short_trace(tmp_path):
    from bench.tests._tiny import tiny_root
    root, bench = tiny_root(tmp_path)
    out = tmp_path / "t.xplane.pb.gz"
    got = sp._record("moe.open", 3, str(out), root=root, require_chip=False,
                     bench=bench)
    assert out.is_file() and len(got["steps"]) >= 3
    d = tmp_path / "unpacked" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(out) as src, open(d / "x.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    program = sp.load_program(str(tmp_path / "unpacked"))
    steps = [s for s in program if s.name == "engine.step"]
    assert [s.args["rows"] for s in steps] == [int(sum(q)) for _, _, q, _
                                               in got["steps"]]
