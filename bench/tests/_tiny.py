"""A BENCHMARK-shaped cell set over the tiny CPU configurations in
``data/``, laid out as files in a temporary checkout root."""

import json
import pathlib
import shutil

DATA = pathlib.Path(__file__).resolve().parent / "data"


def tiny_root(tmp: pathlib.Path) -> tuple:
    """(root, bench dict) with the tiny traffic mixes as files under root."""
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for name in ("tiny-open", "tiny-closed"):
        shutil.copy(DATA / f"{name}.json",
                    tmp / "bench" / "traffic" / f"{name}.json")
    bench = {
        "configs": [{"name": "tiny-moe", "file": str(DATA / "tiny-moe.json")},
                    {"name": "tiny-mla", "file": str(DATA / "tiny-mla.json")}],
        "workloads": [
            {"name": "moe.open", "config": "tiny-moe", "traffic": "tiny-open",
             "chips": 1},
            {"name": "moe.closed", "config": "tiny-moe",
             "traffic": "tiny-closed", "chips": 1},
            {"name": "mla.open", "config": "tiny-mla", "traffic": "tiny-open",
             "chips": 1}],
        "end_to_end": [
            {"name": "ttft_mean_ms", "unit": "ms",
             "workloads": ["moe.open", "mla.open"]},
            {"name": "itl_p95_ms", "unit": "ms",
             "workloads": ["moe.open", "mla.open"]},
            {"name": "output_tok_s", "unit": "tokens/s",
             "workloads": ["moe.closed"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "queue_wait_mean_ms.chat", "unit": "ms",
             "workloads": ["moe.open", "mla.open"]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, bench
