"""A configuration file, read into the sizes that the reference and the
harness both use.

``configs/<name>.json`` holds the model's published ``config.json`` keys
(those that say something about its shape), the keys changed from the
source listed under ``reduced``, and free-form notes.  ``Arch`` is what
the plain reference computes from; ``program_config`` maps it onto the
serving system's own ``ModelConfig``.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    attention: str          # "gqa" | "mla"
    n_layers: int
    first_dense: int        # leading layers with a dense FFN
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int           # q/k head size (MLA: the non-rope part)
    v_head_dim: int
    rope_dim: int           # MLA: decoupled rope dims; GQA: head_dim
    q_rank: int             # MLA only: the program's query rank
    q_lora: int             # MLA only: q_lora_rank as stated, 0 for null
    kv_rank: int            # MLA only
    d_ff: int               # dense FFN width
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int
    vocab: int
    eps: float
    rope_theta: float
    dtype: str
    overrides: dict = dataclasses.field(default_factory=dict, hash=False,
                                        compare=False)


# Semantics that both the reference and the serving program compute.  A
# configuration that states anything else is refused here, before a run,
# rather than compared against a model that computes something different.
_REQUIRED = {
    "phimoe": {"hidden_act": "silu", "tie_word_embeddings": False,
               "attention_bias": False, "lm_head_bias": False,
               "rope_scaling": None},
    "deepseek_v2": {"hidden_act": "silu", "tie_word_embeddings": False,
                    "attention_bias": False, "scoring_func": "softmax",
                    "topk_method": "greedy", "n_group": 1, "topk_group": 1,
                    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
                    "moe_layer_freq": 1, "rope_scaling": None},
}


def load_config(path) -> dict:
    path = pathlib.Path(path)
    if not path.is_absolute():
        path = HERE.parent / path
    return json.loads(path.read_text())


def _plain_rope(rs):
    """YaRN at factor 1 interpolates no frequency and scales neither the
    rotary tables nor the softmax (its mscale is 1 at factor 1): it is
    plain rotary embeddings, stated as ``None``."""
    if isinstance(rs, dict) and rs.get("type") == "yarn" \
            and rs.get("factor") == 1:
        return None
    return rs


def arch_from(c: dict) -> Arch:
    mt = c["model_type"]
    if mt not in _REQUIRED:
        raise ValueError(f"model_type {mt!r} is not one the benchmark can "
                         f"run (have {sorted(_REQUIRED)})")
    stated = dict(c, rope_scaling=_plain_rope(c.get("rope_scaling")))
    for k, v in _REQUIRED[mt].items():
        if stated.get(k, v) != v:
            raise ValueError(f"{c.get('name', mt)}: {k}={c[k]!r}; the "
                             f"serving program computes {k}={v!r} only")
    d, nh = c["hidden_size"], c["num_attention_heads"]
    if mt == "phimoe":
        hd = c.get("head_dim", d // nh)
        return Arch(
            name=c["name"], attention="gqa", n_layers=c["num_hidden_layers"],
            first_dense=0, d_model=d, n_heads=nh,
            n_kv_heads=c["num_key_value_heads"], head_dim=hd, v_head_dim=hd,
            rope_dim=hd, q_rank=0, q_lora=0, kv_rank=0,
            d_ff=c["intermediate_size"], n_experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            d_expert=c["intermediate_size"], n_shared=0,
            vocab=c["vocab_size"], eps=c["rms_norm_eps"],
            rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
            overrides=_overrides(c))
    return Arch(
        name=c["name"], attention="mla", n_layers=c["num_hidden_layers"],
        first_dense=c["first_k_dense_replace"], d_model=d, n_heads=nh,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_dim=c["qk_rope_head_dim"], q_rank=q_rank(c),
        q_lora=c["q_lora_rank"] or 0,
        kv_rank=c["kv_lora_rank"], d_ff=c["intermediate_size"],
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        n_shared=c["n_shared_experts"], vocab=c["vocab_size"],
        eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        dtype=c["torch_dtype"], overrides=_overrides(c))


def q_rank(c: dict) -> int:
    """The rank of the query path the serving program runs.  With
    ``q_lora_rank`` null (queries projected directly, as published for
    DeepSeek-V2-Lite) the program still projects through a full-rank
    ``d_model`` x ``d_model`` ``w_dq`` and its RMSNorm: a departure that the
    configuration's file states, and that the reference computes too."""
    return c["q_lora_rank"] or c["hidden_size"]


def _overrides(c: dict) -> dict:
    """``serve_overrides``: serving knobs the file sets away from "auto".
    Only switching kernels off is known, and each needs its reason."""
    ov = c.get("serve_overrides")
    if not ov:
        return {}
    if set(ov) != {"kernels_off", "reason"} or not ov["reason"]:
        raise ValueError(f"{c['name']}: serve_overrides takes kernels_off "
                         f"and its reason, got {sorted(ov)}")
    return ov


def program_config(a: Arch):
    """The serving system's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig
    common = dict(name=a.name, family="moe", n_layers=a.n_layers,
                  d_model=a.d_model, n_heads=a.n_heads,
                  n_kv_heads=a.n_kv_heads, d_ff=a.d_ff, vocab_size=a.vocab,
                  head_dim=a.head_dim, rope_theta=a.rope_theta,
                  n_experts=a.n_experts, top_k=a.top_k,
                  d_expert=a.d_expert, n_shared_experts=a.n_shared,
                  first_dense_layers=a.first_dense, activation="swiglu",
                  norm_eps=a.eps, dtype=a.dtype)
    if a.attention == "mla":
        common.update(attention="mla", kv_lora_rank=a.kv_rank,
                      q_lora_rank=a.q_lora, rope_head_dim=a.rope_dim,
                      v_head_dim=a.v_head_dim)
    else:
        common.update(attention="gqa")
    cfg = ModelConfig(**common)
    if cfg.padded_vocab != cfg.vocab_size:
        raise ValueError(f"{a.name}: vocab {a.vocab} is padded by the "
                         "program; the reference does not model padding")
    return cfg
