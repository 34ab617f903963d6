"""Weight loader/exporter — the paper's online-stage "weight loader &
partitioner" (§III-A).

Converts between a flat HF-transformers-style checkpoint dict
(``model.layers.{i}.self_attn.q_proj.weight`` with (out, in)-major Linear
layout) and this framework's stacked-scan pytree for the llama-family
architectures (smollm, minitron, gemma*, qwen2-vl text stack).

The loader is where the partitioner's NamedShardings would be applied on a
real cluster: ``load_llama_style(..., plan=...)`` device_puts each stacked
tensor with its plan sharding, so weights stream from host to their shards
without ever materializing replicated.

*gemma's tied embedding + GeGLU map 1:1; MLA/MoE archs have their own
native checkpoint layouts and are out of scope for this HF mapping.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.partitioner import NULL_PLAN, ShardingPlan
from repro.models.model import model_spec, param_axes
from repro.models.param import P, is_p


def _llama_names(cfg: ModelConfig, i: int) -> dict:
    base = f"model.layers.{i}."
    names = {
        "attn.wq": base + "self_attn.q_proj.weight",
        "attn.wk": base + "self_attn.k_proj.weight",
        "attn.wv": base + "self_attn.v_proj.weight",
        "attn.wo": base + "self_attn.o_proj.weight",
        "attn.norm": base + "input_layernorm.weight",
        "mlp.w_in": base + "mlp.up_proj.weight",
        "mlp.w_out": base + "mlp.down_proj.weight",
        "mlp.norm": base + "post_attention_layernorm.weight",
    }
    if cfg.activation in ("swiglu", "geglu"):      # gated MLPs only
        names["mlp.w_gate"] = base + "mlp.gate_proj.weight"
    return names


def _to_ours(key: str, arr: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    """HF Linear (out, in) -> our einsum layouts."""
    h, nq, nkv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    if key == "attn.wq":
        return arr.reshape(nq, hd, h).transpose(2, 0, 1)       # (h, nq, hd)
    if key in ("attn.wk", "attn.wv"):
        return arr.reshape(nkv, hd, h).transpose(2, 0, 1)      # (h, nkv, hd)
    if key == "attn.wo":
        return arr.reshape(h, nq, hd).transpose(1, 2, 0)       # (nq, hd, h)
    if key in ("mlp.w_gate", "mlp.w_in"):
        return arr.T                                           # (h, f)
    if key == "mlp.w_out":
        return arr.T                                           # (f, h)
    return arr   # norms map 1:1 (our rms_norm uses the (1 + w) convention
                 # with zero-init, matching HF weights shifted by the loader
                 # caller when needed)


def _from_ours(key: str, arr: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    h, nq, nkv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    if key == "attn.wq":
        return arr.transpose(1, 2, 0).reshape(nq * hd, h)
    if key in ("attn.wk", "attn.wv"):
        return arr.transpose(1, 2, 0).reshape(nkv * hd, h)
    if key == "attn.wo":
        return arr.transpose(2, 0, 1).reshape(h, nq * hd)
    if key in ("mlp.w_gate", "mlp.w_in", "mlp.w_out"):
        return arr.T
    return arr


def _supported(cfg: ModelConfig) -> bool:
    return (cfg.family in ("dense", "vlm") and cfg.attention == "gqa"
            and not cfg.is_moe)


def export_llama_style(params, cfg: ModelConfig) -> dict:
    """Our pytree -> flat HF-style dict (llama-family dense archs)."""
    assert _supported(cfg), f"{cfg.name}: not a llama-family dense arch"
    flat = {}
    v = cfg.vocab_size
    flat["model.embed_tokens.weight"] = np.asarray(params["embed"])[:v]
    flat["model.norm.weight"] = np.asarray(params["final_norm"])
    if not cfg.tie_embeddings:
        flat["lm_head.weight"] = np.asarray(params["lm_head"]).T[:v]
    stack = params["groups"][0]
    for i in range(cfg.n_layers):
        for key, name in _llama_names(cfg, i).items():
            sub, leaf = key.split(".")
            arr = np.asarray(stack[sub][leaf][i])
            flat[name] = _from_ours(key, arr, cfg)
    return flat


def param_shardings(cfg: ModelConfig, plan: ShardingPlan):
    """The plan's NamedSharding for every weight (divisibility-aware, as
    ``load_llama_style`` places them), or None without a mesh."""
    if not plan.enabled:
        return None
    shapes = jax.tree.map(lambda p: p.shape, model_spec(cfg), is_leaf=is_p)
    return jax.tree.map(lambda shape, ax: plan.sharding_for(shape, ax),
                        shapes, param_axes(cfg),
                        is_leaf=lambda t: isinstance(t, tuple)
                        and all(isinstance(d, int) for d in t))


def load_llama_style(flat: dict, cfg: ModelConfig,
                     plan: ShardingPlan = NULL_PLAN,
                     dtype=jnp.float32) -> dict:
    """Flat HF-style dict -> our pytree (optionally sharded via the plan)."""
    assert _supported(cfg), f"{cfg.name}: not a llama-family dense arch"
    spec = model_spec(cfg)
    axes = param_axes(cfg)

    def put(arr, p_decl: P, ax):
        arr = np.asarray(arr, dtype=jnp.dtype(dtype).name)
        if tuple(arr.shape) != tuple(p_decl.shape):     # vocab padding
            padded = np.zeros(p_decl.shape, arr.dtype)
            padded[tuple(slice(0, s) for s in arr.shape)] = arr
            arr = padded
        sh = plan.sharding_for(arr.shape, ax) if plan.enabled else None
        return jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr)

    out = {"embed": put(flat["model.embed_tokens.weight"], spec["embed"],
                        axes["embed"]),
           "final_norm": put(flat["model.norm.weight"], spec["final_norm"],
                             axes["final_norm"])}
    if not cfg.tie_embeddings:
        out["lm_head"] = put(np.asarray(flat["lm_head.weight"]).T,
                             spec["lm_head"], axes["lm_head"])

    layer_spec = spec["groups"][0]
    layer_axes = axes["groups"][0]
    stacked: dict = {"attn": {}, "mlp": {}}
    for key in _llama_names(cfg, 0):
        sub, leaf = key.split(".")
        per_layer = [
            _to_ours(key, np.asarray(flat[_llama_names(cfg, i)[key]]), cfg)
            for i in range(cfg.n_layers)]
        stacked[sub][leaf] = put(np.stack(per_layer),
                                 layer_spec[sub][leaf],
                                 layer_axes[sub][leaf])
    out["groups"] = [stacked]
    return out


__all__ = ["export_llama_style", "load_llama_style", "param_shardings"]
