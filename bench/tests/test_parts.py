"""The benchmark's parts on their own: traffic, costs, peaks, the
reference's control, and BENCHMARK.json against the files it names."""

import json
import math
import pathlib
import re

import pytest

from bench import arch, costs, harness, peaks, traffic
from bench.tests._tiny import DATA

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _mix(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_open_traffic_same_work_every_seed():
    """A seed draws the token ids alone: sizes, answers and due times are
    the base seed's, the same for every run."""
    mix = _mix("tiny-open")
    a = traffic.schedule(mix, 512, 2 ** 31 + 7, 10.0)
    b = traffic.schedule(mix, 512, 2 ** 31 + 8, 10.0)
    assert len(a) == len(b) == math.ceil(mix["rate_rps"] * 10.0)
    assert [len(i.prompt) for i in a] == [len(i.prompt) for i in b]
    assert [(i.n_out, i.due) for i in a] == [(i.n_out, i.due) for i in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert a[0].due == 0.0 and all(i.due < 10.0 for i in a)
    assert all(0 <= int(t) < 512 for i in a for t in i.prompt)
    again = traffic.schedule(mix, 512, 2 ** 31 + 7, 10.0)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               for x, y in zip(a, again))


def test_a_lower_rate_serves_a_prefix_of_the_same_requests():
    """Sizes, answers and ids come from streams of their own, so a knee
    sweep's rates differ in how many of one request list they send."""
    mix = _mix("tiny-open")
    lo = traffic.schedule(dict(mix, rate_rps=6.0), 512, 5, 10.0)
    hi = traffic.schedule(dict(mix, rate_rps=12.0), 512, 5, 10.0)
    assert len(lo) == 60 and len(hi) == 120
    assert [(len(i.prompt), i.n_out) for i in lo] \
        == [(len(i.prompt), i.n_out) for i in hi[:60]]
    assert all((x.prompt == y.prompt).all() for x, y in zip(lo, hi))


def test_closed_traffic_is_queued_at_the_open():
    mix = _mix("tiny-closed")
    a = traffic.schedule(mix, 512, 3, 5.0)
    b = traffic.schedule(mix, 512, 4, 5.0)
    assert len(a) == mix["depth"] and all(i.due == 0.0 for i in a)
    assert [len(i.prompt) for i in a] == [len(i.prompt) for i in b]


def test_lengths_stay_in_their_clip():
    mix = _mix("tiny-open")
    for it in traffic.schedule(mix, 512, 99, 30.0):
        assert mix["prompt"]["min"] <= len(it.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= it.n_out <= mix["output"]["max"]


def test_grouped_gemm_costs():
    flops, nbytes = costs.grouped_gemm(rows=1024, k=4096, n=6400, groups=16)
    assert flops == 2 * 1024 * 4096 * 6400
    assert nbytes == 2 * (1024 * 4096 + 16 * 4096 * 6400 + 1024 * 6400)
    _, few = costs.grouped_gemm(rows=3, k=8, n=8, groups=16)
    assert few == 2 * (3 * 8 + 3 * 8 * 8 + 3 * 8)


def test_attention_costs():
    # 2 new rows over 3 cached ones: pairs 3*2 + (1 + 2) = 9
    flops, nbytes = costs.attention(2, 3, n_q=4, d_qk=8, d_v=8, n_kv=2)
    assert flops == 2 * 4 * 16 * 9
    assert nbytes == 2 * (5 * 2 * 16 + 2 * 4 * 16)
    assert costs.attention(0, 100, 4, 8, 8, 2) == (0.0, 0.0)


def test_roofline_takes_the_larger_term():
    p = peaks.Peaks(bf16_flops=100.0, hbm_bytes_s=10.0,
                    source="test")
    assert costs.roofline_s(1000.0, 10.0, p) == 10.0
    assert costs.roofline_s(10.0, 1000.0, p) == 100.0


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_configuration_stating_other_semantics_is_refused():
    c = arch.load_config(DATA / "tiny-mla.json")
    c["topk_method"] = "group_limited_greedy"
    with pytest.raises(ValueError):
        arch.arch_from(c)


def test_benchmark_json_names_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["bench"]
    names = [c["name"] for c in bench["configs"]] \
        + [w["name"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        conf = arch.load_config(ROOT / c["file"])
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        arch.program_config(arch.arch_from(conf))
        assert conf["correct"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        reported = [m for m in e2e if w["name"] in e2e[m].get(
            "workloads", [w["name"]])]
        assert "setup_s" in reported and len(reported) >= 2
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_metric_reader_falls_back_to_the_stem():
    from bench import readers
    assert harness.metric_reader("step_ms.chat") is readers.step_ms
    assert harness.metric_reader("step_ms.offline") is readers.step_ms
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric.chat")


def test_judge_holds_each_number_to_its_limit():
    limits = {"mean_gap": 0.02}
    ok, compared = harness.judge({"mean_gap": 0.01}, 0, limits)
    assert ok and compared["mean_gap"] == {"value": 0.01, "limit": 0.02}
    assert not harness.judge({"mean_gap": 0.03}, 0, limits)[0]
    assert not harness.judge({"mean_gap": 0.01}, 1, limits)[0]
    assert not harness.judge(None, 0, limits)[0]
