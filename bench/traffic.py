"""One general generator for every traffic mix: ``traffic/<name>.json``.

A mix is data:

    {"kind": "open" | "closed",
     "rate_rps": 0.2,          # open: offered requests per second
     "depth": 96,              # closed: requests queued before the window
     "prompt": {"median": 1020, "sigma": 0.7, "min": 128, "max": 3072},
     "output": {"median": 129, "sigma": 0.6, "min": 8, "max": 512},
     "base_seed": 20261019,
     "source": "..."}          # where the numbers come from; not read

Lengths are lognormal (median, sigma), rounded and clipped.  Every run's
prompt lengths, answer lengths and inter-arrival gaps are drawn from
``base_seed``, each from a stream of its own, so that the first n requests
of a longer list are the n requests of a shorter one (every rate of a knee
sweep serves a prefix of one request list).  The run's own seed draws only
the token ids (uniform over the vocabulary, no shared prefixes, greedy), so
runs with different seeds do the same work.  Open-loop gaps are
exponential (Poisson arrivals), rescaled so that exactly
``ceil(rate * seconds)`` requests fall due in the window.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    due: float             # seconds after the window opens
    prompt: np.ndarray     # int32 token ids
    n_out: int             # tokens to generate (greedy)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> list:
    """The window's requests, in due order."""
    n = max(1, math.ceil(mix["rate_rps"] * seconds)) \
        if mix["kind"] == "open" else int(mix["depth"])
    def stream(i):
        return np.random.default_rng([int(mix["base_seed"]), i])
    p_len = _lengths(stream(0), mix["prompt"], n)
    o_len = _lengths(stream(1), mix["output"], n)
    gaps = stream(2).exponential(1.0, n)
    rng = np.random.default_rng(int(seed))
    if mix["kind"] == "open":
        # first request due at the window's open, the last gap ends at
        # its close: n arrivals spread over exactly `seconds`
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            * (seconds / gaps.sum())
    else:
        due = np.zeros(n)
    return [Item(float(due[i]),
                 rng.integers(0, vocab, size=int(p_len[i])).astype(np.int32),
                 int(o_len[i])) for i in range(n)]


def hints(mix: dict) -> dict:
    """Workload hints for ``ServeSpec``: the envelope every request fits."""
    return {"prompt_len": int(mix["prompt"]["max"]),
            "max_new_tokens": int(mix["output"]["max"]),
            "arrival_rate": float(mix.get("rate_rps", 0.0))}
