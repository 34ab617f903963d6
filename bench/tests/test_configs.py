"""Each configuration file against the published configuration it carries
(``published``): every published key is in the file under the same key
and with the same value, nested groups whole, except the keys that
``reduced`` lists; and no width differs, whether or not ``reduced`` lists
it.  BENCHMARK.json's ``reduced`` names the same keys."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))

# Keys that give a width or a count of heads, experts or groups: sizes,
# head dims, ranks, expert counts, experts per token, routing groups and
# the vocabulary.  None of them may change, even inside a nested group.
WIDTH = re.compile(r"(_size|_dim|_rank|_heads|_experts|_experts_per_tok"
                   r"|^n_group|^topk_group|^head_dim|expansion)$")


def is_width(key: str) -> bool:
    return bool(WIDTH.search(key))


def _widths(value, path=""):
    """(path, value) of every width key inside a (nested) value."""
    if isinstance(value, dict):
        for k, v in value.items():
            p = f"{path}.{k}" if path else k
            if is_width(k):
                yield p, v
            yield from _widths(v, p)


def test_every_configuration_is_checked():
    names = {p.stem for p in CONFIGS}
    listed = {pathlib.Path(c["file"]).stem for c in
              json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]}
    assert listed <= names


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_file_holds_its_source_except_what_reduced_lists(path):
    conf = json.loads(path.read_text())
    pub, reduced = conf["published"], conf["reduced"]
    assert set(reduced) <= set(pub), "reduced names a key the source lacks"
    for key, value in pub.items():
        assert key in conf, f"{key} is left out"
        if key in reduced:
            assert conf[key] != value, f"{key} is listed but unchanged"
        else:
            assert conf[key] == value, f"{key}: {conf[key]!r} != {value!r}"


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_no_width_differs(path):
    conf = json.loads(path.read_text())
    pub = conf["published"]
    for key in conf["reduced"]:
        assert not is_width(key), f"reduced lists the width {key}"
    assert dict(_widths(pub)) == {p: v for p, v in _widths(
        {k: conf[k] for k in pub})}


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_benchmark_names_the_same_reduced_keys(path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"]
             if pathlib.Path(c["file"]).name == path.name]
    if entry:
        assert entry[0]["reduced"] == json.loads(path.read_text())["reduced"]


def test_width_keys_are_recognised():
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "vocab_size", "qk_nope_head_dim", "kv_lora_rank",
              "q_lora_rank", "num_attention_heads", "num_key_value_heads",
              "n_routed_experts", "num_local_experts", "n_shared_experts",
              "num_experts_per_tok", "n_group", "topk_group", "head_dim"):
        assert is_width(k), k
    for k in ("num_hidden_layers", "norm_topk_prob", "rope_scaling",
              "rms_norm_eps", "routed_scaling_factor", "factor"):
        assert not is_width(k), k


def test_a_changed_width_is_caught(tmp_path):
    """A routing-group width changed, with and without reduced naming it."""
    conf = json.loads((ROOT / "bench" / "configs"
                       / "deepseek-v2-lite-l9.json").read_text())
    conf["topk_group"] = 3
    conf["reduced"] = conf["reduced"] + ["topk_group"]
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(conf))
    with pytest.raises(AssertionError):
        test_no_width_differs(p)
    conf["reduced"] = conf["reduced"][:-1]
    p.write_text(json.dumps(conf))
    with pytest.raises(AssertionError):
        test_file_holds_its_source_except_what_reduced_lists(p)
