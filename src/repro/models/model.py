"""Generic decoder covering all six assigned architecture families.

One ``ModelConfig`` + this module = a runnable model for any of:
  dense / vlm   GQA|MQA|MLA attention + dense MLP        (qwen2-vl, gemma,
                                                           smollm, minicpm3,
                                                           minitron)
  moe           attention + MixServe hybrid TP-EP MoE     (phi3.5, deepseek-v2)
  ssm           RWKV6 time-mix + channel-mix              (rwkv6)
  hybrid        (rec, rec, attn) Griffin pattern          (recurrentgemma)
  audio         whisper enc-dec (conv/mel frontend stub)  (whisper-tiny)

Layers of the same kind are *stacked* on a leading axis and driven by
``lax.scan`` so the HLO stays O(1) in depth (62-layer minicpm3 at 512 devices
must compile on a CPU host).  Heterogeneous stacks (hybrid pattern,
first-dense-layer MoE) are split into homogeneous groups scanned separately.

Three execution modes share one ``forward``:
  cache=None                    train / stateless forward
  cache given, seq > 1          prefill INTO preallocated cache buffers
  cache given, seq == 1         decode (single token, seq-sharded KV)

Decode KV caches are sharded along the *sequence* axis over the TP ("model")
mesh axis — the universal scheme that works for MQA (kv=1), GQA (any head
count) and MLA (headless latent), keeping per-chip cache bytes ~1/d_TP.

The plan's ``KernelPolicy`` (``plan.kernels``) rides through every layer
call here: cache-backed prefill and the unified mixed step run the ragged
``flash_chunk`` Pallas kernel, single-token decode its ``flash_decode``
specialization, and the MoE block the ``topk_gate``/fused-permute/
``moe_gemm`` pipeline when enabled (routing table: docs/kernels.md).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.partitioner import NULL_PLAN, ShardingPlan
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.models.param import P, abstract_tree, init_tree, stack


# ---------------------------------------------------------------------------
# Layer grouping (homogeneous scan groups)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Group:
    kind: str            # dense | moe | rwkv | rec | attn | xdec | pattern
    count: int           # scan length
    sub: tuple = ()      # pattern: per-repeat sub-layer kinds


def layer_plan(cfg: ModelConfig) -> tuple[Group, ...]:
    if cfg.family == "moe":
        g = []
        if cfg.first_dense_layers:
            g.append(Group("dense", cfg.first_dense_layers))
        g.append(Group("moe", cfg.n_layers - cfg.first_dense_layers))
        return tuple(g)
    if cfg.family == "ssm":
        return (Group("rwkv", cfg.n_layers),)
    if cfg.family == "hybrid":
        pat = cfg.block_pattern
        reps, rem = divmod(cfg.n_layers, len(pat))
        g = []
        if reps:
            g.append(Group("pattern", reps, pat))
        # remainder layers continue the pattern prefix, grouped by equal kind
        i = 0
        while i < rem:
            j = i
            while j < rem and pat[j] == pat[i]:
                j += 1
            g.append(Group(pat[i], j - i))
            i = j
        return tuple(g)
    if cfg.family == "audio":
        return (Group("xdec", cfg.n_layers),)
    return (Group("dense", cfg.n_layers),)   # dense / vlm


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ModelConfig) -> dict:
    return L.mla_spec(cfg) if cfg.attention == "mla" else L.gqa_spec(cfg)


def xattn_spec(cfg: ModelConfig) -> dict:
    """Cross-attention (whisper decoder -> encoder output)."""
    h, nq, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": P((h, nq, hd), ("embed", "heads", "head_dim")),
        "wk": P((h, nq, hd), ("embed", "heads", "head_dim")),
        "wv": P((h, nq, hd), ("embed", "heads", "head_dim")),
        "wo": P((nq, hd, h), ("heads", "head_dim", "embed")),
        "norm": P((h,), ("embed",), init="zeros"),
    }


def sublayer_spec(cfg: ModelConfig, kind: str) -> dict:
    if kind == "dense":
        return {"attn": _attn_spec(cfg), "mlp": L.mlp_spec(cfg)}
    if kind == "moe":
        return {"attn": _attn_spec(cfg), "moe": MOE.moe_spec(cfg)}
    if kind == "rwkv":
        return {"tm": S.rwkv6_spec(cfg), "cm": S.rwkv6_channel_mix_spec(cfg)}
    if kind == "rec":
        return {"rglru": S.rglru_spec(cfg), "mlp": L.mlp_spec(cfg)}
    if kind == "attn":
        return {"attn": L.gqa_spec(cfg), "mlp": L.mlp_spec(cfg)}
    if kind == "xdec":
        return {"attn": L.gqa_spec(cfg), "xattn": xattn_spec(cfg),
                "mlp": L.mlp_spec(cfg)}
    raise KeyError(kind)


def group_spec(cfg: ModelConfig, g: Group) -> dict:
    if g.kind == "pattern":
        per = {f"l{i}": sublayer_spec(cfg, k) for i, k in enumerate(g.sub)}
        return stack(per, g.count)
    return stack(sublayer_spec(cfg, g.kind), g.count)


def enc_layer_spec(cfg: ModelConfig) -> dict:
    e = cfg.encoder
    d, nh, f = e.d_model, e.n_heads, e.d_ff
    hd = d // nh
    return {
        "wq": P((d, nh, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, nh, hd), ("embed", "heads", "head_dim")),
        "wv": P((d, nh, hd), ("embed", "heads", "head_dim")),
        "wo": P((nh, hd, d), ("heads", "head_dim", "embed")),
        "norm": P((d,), ("embed",), init="zeros"),
        "mlp_in": P((d, f), ("embed", "ffn")),
        "mlp_out": P((f, d), ("ffn", "embed")),
        "mlp_norm": P((d,), ("embed",), init="zeros"),
    }


def model_spec(cfg: ModelConfig) -> dict:
    h, v = cfg.d_model, cfg.padded_vocab
    spec: dict[str, Any] = {
        "embed": P((v, h), ("vocab", "embed"), scale=0.02),
        "final_norm": P((h,), ("embed",), init="zeros"),
        "groups": [group_spec(cfg, g) for g in layer_plan(cfg)],
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = P((h, v), ("embed", "vocab"))
    if cfg.family == "audio":
        e = cfg.encoder
        spec["enc"] = {
            "pos": P((e.n_frames, e.d_model), (None, "embed"), scale=0.02),
            "layers": stack(enc_layer_spec(cfg), e.n_layers),
            "final_norm": P((e.d_model,), ("embed",), init="zeros"),
        }
    return spec


def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    return init_tree(key, model_spec(cfg), dtype)


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    return abstract_tree(model_spec(cfg), dtype)


def param_axes(cfg: ModelConfig):
    from repro.models.param import axes_tree
    return axes_tree(model_spec(cfg))


def count_params(cfg: ModelConfig) -> int:
    from repro.models.param import param_count
    return param_count(model_spec(cfg))


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _cache_for(cfg: ModelConfig, kind: str, count: int, batch: int,
               max_len: int, mk, dtype) -> dict:
    h = cfg.d_model
    if kind in ("dense", "moe", "xdec"):
        if cfg.attention == "mla":
            c = {"c": mk((count, batch, max_len, cfg.kv_lora_rank), dtype),
                 "kr": mk((count, batch, max_len, cfg.rope_head_dim), dtype)}
        else:
            nkv, hd = cfg.n_kv_heads, cfg.head_dim
            c = {"k": mk((count, batch, max_len, nkv, hd), dtype),
                 "v": mk((count, batch, max_len, nkv, hd), dtype)}
        if kind == "xdec":
            e = cfg.encoder
            nq, hd = cfg.n_heads, cfg.head_dim
            c["xk"] = mk((count, batch, e.n_frames, nq, hd), dtype)
            c["xv"] = mk((count, batch, e.n_frames, nq, hd), dtype)
        return c
    if kind == "rwkv":
        nh = max(1, h // 64)
        hd = h // nh
        return {"state": mk((count, batch, nh, hd, hd), jnp.float32),
                "x_tm": mk((count, batch, 1, h), dtype),
                "x_cm": mk((count, batch, 1, h), dtype)}
    if kind == "rec":
        w, cw = cfg.lru_width, cfg.conv1d_width
        return {"lru": mk((count, batch, w), jnp.float32),
                "conv": mk((count, batch, cw - 1, w), dtype)}
    if kind == "attn":
        nkv, hd, W = cfg.n_kv_heads, cfg.head_dim, cfg.window_size
        W = min(W, max_len) if W else max_len
        return {"k": mk((count, batch, W, nkv, hd), dtype),
                "v": mk((count, batch, W, nkv, hd), dtype),
                "kpos": mk((count, batch, W), jnp.int32)}
    raise KeyError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, abstract: bool = False):
    if abstract:
        mk = lambda s, dt: jax.ShapeDtypeStruct(s, dt)
    else:
        def mk(s, dt):
            if dt == jnp.int32:           # ring positions start invalid
                return jnp.full(s, -1, dt)
            return jnp.zeros(s, dt)
    groups = []
    for g in layer_plan(cfg):
        if g.kind == "pattern":
            groups.append({f"l{i}": _cache_for(cfg, k, g.count, batch,
                                               max_len, mk, dtype)
                           for i, k in enumerate(g.sub)})
        else:
            groups.append(_cache_for(cfg, g.kind, g.count, batch, max_len,
                                     mk, dtype))
    length = (jax.ShapeDtypeStruct((), jnp.int32) if abstract
              else jnp.zeros((), jnp.int32))
    return {"groups": groups, "length": length}


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int, pool_pages: int, dtype=jnp.bfloat16):
    """Paged KV cache: per-group KV *pools* + per-slot block tables.

    Pools are ``(count, pool_pages, page_size, ...)`` — page 0..P-1 of a
    global free pool instead of ``(count, batch, max_len, ...)`` per-slot
    rows.  ``block_tables`` (batch, max_len // page_size) int32 maps a
    slot's logical block b to its physical page (−1 = unallocated); the
    host-side allocator (``serving.kv_cache.PagedKVCache``) owns the
    tables, refcounts and the prefix index.  Attention-cached kinds only —
    recurrent/ring state has no page structure to share.
    """
    if max_len % page_size:
        raise ValueError(f"page_size {page_size} must divide "
                         f"max_len {max_len}")
    max_blocks = max_len // page_size
    groups = []
    for g in layer_plan(cfg):
        if g.kind not in ("dense", "moe"):
            raise NotImplementedError(
                f"paged KV cache unsupported for layer kind {g.kind!r} — "
                "this family serves through the dense legacy path")
        if cfg.attention == "mla":
            c = {"c": jnp.zeros((g.count, pool_pages, page_size,
                                 cfg.kv_lora_rank), dtype),
                 "kr": jnp.zeros((g.count, pool_pages, page_size,
                                  cfg.rope_head_dim), dtype)}
        else:
            nkv, hd = cfg.n_kv_heads, cfg.head_dim
            c = {"k": jnp.zeros((g.count, pool_pages, page_size, nkv, hd),
                                dtype),
                 "v": jnp.zeros((g.count, pool_pages, page_size, nkv, hd),
                                dtype)}
        groups.append(c)
    return {"groups": groups,
            "length": jnp.zeros((batch,), jnp.int32),
            "block_tables": jnp.full((batch, max_blocks), -1, jnp.int32)}


def cache_axes(cfg: ModelConfig, batch: int, max_len: int):
    """Logical axes matching init_cache's pytree (for shardings)."""
    def mk(shape, dt):
        if len(shape) >= 3 and shape[2] in (max_len,
                                            min(cfg.window_size or max_len,
                                                max_len)):
            # (layers, batch, kv_seq, ...)
            return ("layers", "batch", "kv_seq") + (None,) * (len(shape) - 3)
        if len(shape) >= 2 and shape[1] == batch:
            return ("layers", "batch") + (None,) * (len(shape) - 2)
        return ("layers",) + (None,) * (len(shape) - 1)
    groups = []
    for g in layer_plan(cfg):
        if g.kind == "pattern":
            groups.append({f"l{i}": jax.tree.map(
                lambda a: mk(a.shape, None),
                _cache_for(cfg, k, g.count, batch, max_len,
                           lambda s, dt: jax.ShapeDtypeStruct(s, jnp.float32),
                           jnp.float32))
                for i, k in enumerate(g.sub)})
        else:
            groups.append(jax.tree.map(
                lambda a: mk(a.shape, None),
                _cache_for(cfg, g.kind, g.count, batch, max_len,
                           lambda s, dt: jax.ShapeDtypeStruct(s, jnp.float32),
                           jnp.float32)))
    return {"groups": groups, "length": ()}


# ---------------------------------------------------------------------------
# Sub-layer application
# ---------------------------------------------------------------------------

def _ring_from_prefill(k, v, W: int, s: int):
    """Build a ring-buffer cache holding the last W tokens (slot = pos % W)."""
    b = k.shape[0]
    if s >= W:
        kc = jnp.roll(k[:, s - W:], shift=s % W, axis=1)
        vc = jnp.roll(v[:, s - W:], shift=s % W, axis=1)
        kpos = jnp.roll(jnp.arange(s - W, s, dtype=jnp.int32), shift=s % W)
    else:
        pad = ((0, 0), (0, W - s), (0, 0), (0, 0))
        kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
        kpos = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                                jnp.full((W - s,), -1, jnp.int32)])
    return kc, vc, jnp.broadcast_to(kpos, (b, W))


def _local_attn(p, x, cfg: ModelConfig, plan: ShardingPlan, c, length):
    """Sliding-window GQA layer (recurrentgemma).  Ring-buffer cache."""
    b, s, h = x.shape
    W = c["k"].shape[1] if c is not None else cfg.window_size
    xn = L.rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsh,hnd->bsnd", xn, p["wq"])
    k = jnp.einsum("bsh,hnd->bsnd", xn, p["wk"])
    v = jnp.einsum("bsh,hnd->bsnd", xn, p["wv"])
    idx = 0 if c is None else length
    positions = jnp.atleast_2d(L.positions_from(idx, s))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    if c is None:
        out = L.chunked_attention(q, k, v, causal=True,
                                  window=cfg.window_size)
        new_c = None
    elif s > 1:  # (chunked) prefill into ring (uniform batch, scalar idx)
        assert s >= W or cfg.window_size >= W, \
            "chunked prefill requires chunk >= window (ring rebuild)"
        # chunk i > 0 must see the previous chunk's ring tail: attend over
        # [ring slots ; fresh chunk] with explicit positions.
        kpos_old = c["kpos"][0] if c["kpos"].ndim == 2 else c["kpos"]
        k_cat = jnp.concatenate([c["k"], k], axis=1)
        v_cat = jnp.concatenate([c["v"], v], axis=1)
        pos_cat = jnp.concatenate(
            [kpos_old, jnp.arange(s, dtype=jnp.int32) + idx])
        out = L.chunked_attention(q, k_cat, v_cat, q_offset=idx,
                                  causal=True, window=cfg.window_size,
                                  k_positions=pos_cat)
        kc, vc, kpos = _ring_from_prefill(k, v, W, s)
        # positions are chunk-local in _ring_from_prefill; shift by idx
        kpos = jnp.where(kpos >= 0, kpos + idx, kpos)
        new_c = {"k": kc, "v": vc, "kpos": kpos}
    else:        # decode against ring (slot = position % W)
        idx_vec = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,))
        slot = idx % W
        kc = L.write_cache(c["k"], k, slot)
        vc = L.write_cache(c["v"], v, slot)
        kpos = L.write_cache(c["kpos"][..., None],
                             idx_vec[:, None, None], slot)[..., 0]
        kc = plan.constrain(kc, "batch", "kv_seq", None, None)
        vc = plan.constrain(vc, "batch", "kv_seq", None, None)
        out = L.decode_attention(q, kc, vc,
                                 q_positions=L.positions_from(idx, s),
                                 window=cfg.window_size, k_positions=kpos)
        new_c = {"k": kc, "v": vc, "kpos": kpos}
    out = jnp.einsum("bsnd,ndh->bsh", out, p["wo"])
    return plan.constrain(out, "batch", "seq_resid", "embed"), new_c


def _cross_attn(p, x, cfg: ModelConfig, plan: ShardingPlan, xk, xv):
    """Whisper decoder cross-attention over (cached) encoder K/V."""
    xn = L.rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsh,hnd->bsnd", xn, p["wq"])
    out = L.chunked_attention(q, xk, xv, causal=False)
    out = jnp.einsum("bsnd,ndh->bsh", out, p["wo"])
    return plan.constrain(out, "batch", "seq_resid", "embed")


def apply_sublayer(kind: str, p, x, c, *, cfg: ModelConfig,
                   plan: ShardingPlan, positions, length, enc_out=None,
                   q_lens=None, block_tables=None, with_stats: bool = False):
    """One residual layer.  Returns (x, new_cache_or_None, aux) — plus a
    per-expert routed-count vector when ``with_stats`` (zeros for non-MoE
    kinds, so the scan carry stays homogeneous).

    ``q_lens`` (b,) marks the unified mixed prefill/decode serving step:
    per-slot ragged query counts against per-slot cache offsets.  Only
    attention-cached kinds support it — the recurrent/ring kinds advance
    their state by every row and cannot mask a ragged tail.
    ``block_tables`` (b, max_blocks) marks a paged cache: ``c`` holds KV
    *pools* and reads/writes go through the per-slot page indirection."""
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((max(cfg.n_experts, 1),), jnp.int32)

    def _ret(x, new_c, aux, counts=counts):
        return (x, new_c, aux, counts) if with_stats else (x, new_c, aux)
    if q_lens is not None and kind not in ("dense", "moe"):
        raise NotImplementedError(
            f"unified mixed step (q_lens) unsupported for layer kind "
            f"{kind!r} — serve this family through the legacy engine path")

    if kind in ("dense", "moe", "xdec"):
        if cfg.attention == "mla":
            mla_cache = None if c is None else (c["c"], c["kr"], length)
            a_out, new_kv = L.mla_attention(p["attn"], x, cfg, plan,
                                            positions=positions,
                                            cache=mla_cache, q_lens=q_lens,
                                            block_table=block_tables)
            new_c = None if c is None else {"c": new_kv[0], "kr": new_kv[1]}
        else:
            kv_view = None if c is None else L.KVView(c["k"], c["v"], length,
                                                      block_tables)
            a_out, new_kv = L.gqa_attention(p["attn"], x, cfg, plan,
                                            positions=positions,
                                            cache=kv_view, q_lens=q_lens)
            new_c = None if c is None else {"k": new_kv[0], "v": new_kv[1]}
        x = x + a_out

        if kind == "xdec":
            if c is not None and enc_out is None:      # decode: cached enc KV
                xk, xv = c["xk"], c["xv"]
            else:                                       # prefill: fresh enc KV
                xk = jnp.einsum("bsh,hnd->bsnd", enc_out, p["xattn"]["wk"])
                xv = jnp.einsum("bsh,hnd->bsnd", enc_out, p["xattn"]["wv"])
            x = x + _cross_attn(p["xattn"], x, cfg, plan, xk, xv)
            if new_c is not None:
                new_c["xk"], new_c["xv"] = xk, xv

        if kind == "moe":
            if with_stats:
                m_out, aux, counts = MOE.moe_block(p["moe"], x, cfg, plan,
                                                   with_stats=True)
            else:
                m_out, aux = MOE.moe_block(p["moe"], x, cfg, plan)
            x = x + m_out
        else:
            x = x + L.mlp(p["mlp"], x, cfg, plan)
        return _ret(x, new_c, aux, counts)

    if kind == "rwkv":
        st = (None, None) if c is None else (c["state"], c["x_tm"])
        t_out, (state, x_tm) = S.rwkv6_time_mix(p["tm"], x, cfg, plan,
                                                state=st[0], x_prev=st[1])
        x = x + t_out
        cm_prev = None if c is None else c["x_cm"]
        c_out, x_cm = S.rwkv6_channel_mix(p["cm"], x, cfg, plan,
                                          x_prev=cm_prev)
        x = x + c_out
        new_c = None if c is None else {"state": state, "x_tm": x_tm,
                                        "x_cm": x_cm}
        return _ret(x, new_c, aux)

    if kind == "rec":
        st = (None, None) if c is None else (c["lru"], c["conv"])
        r_out, (lru, conv) = S.rglru_block(p["rglru"], x, cfg, plan,
                                           state=st[0], conv_state=st[1])
        x = x + r_out
        x = x + L.mlp(p["mlp"], x, cfg, plan)
        new_c = None if c is None else {"lru": lru, "conv": conv}
        return _ret(x, new_c, aux)

    if kind == "attn":
        a_out, new_c = _local_attn(p["attn"], x, cfg, plan, c, length)
        x = x + a_out
        x = x + L.mlp(p["mlp"], x, cfg, plan)
        return _ret(x, new_c, aux)

    raise KeyError(kind)


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------

def encode_audio(params, frames, cfg: ModelConfig, plan: ShardingPlan):
    """frames: (b, n_frames, d_enc) — precomputed conv/mel stub embeddings."""
    e = cfg.encoder
    p_enc = params["enc"]
    x = frames + p_enc["pos"][None]
    x = plan.constrain(x, "batch", "seq", "embed")

    def body(x, p):
        xn = L.rms_norm(x, p["norm"], cfg.norm_eps)
        q = jnp.einsum("bsh,hnd->bsnd", xn, p["wq"])
        k = jnp.einsum("bsh,hnd->bsnd", xn, p["wk"])
        v = jnp.einsum("bsh,hnd->bsnd", xn, p["wv"])
        a = L.chunked_attention(q, k, v, causal=False)     # bidirectional
        x = x + jnp.einsum("bsnd,ndh->bsh", a, p["wo"])
        xn = L.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        x = x + jax.nn.gelu(xn @ p["mlp_in"], approximate=True) @ p["mlp_out"]
        return x, None

    x, _ = jax.lax.scan(body, x, p_enc["layers"])
    return L.rms_norm(x, p_enc["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("logits", "cache", "aux", "expert_counts"),
                   meta_fields=())
@dataclasses.dataclass
class Output:
    logits: jax.Array
    cache: Optional[dict]
    aux: jax.Array          # router load-balance loss (0 for non-MoE)
    # per-expert routed-slot counts summed over MoE layers, (n_experts,)
    # int32 — only populated by forward(expert_stats=True), else None
    expert_counts: Optional[jax.Array] = None


def forward(params, cfg: ModelConfig, plan: ShardingPlan = NULL_PLAN, *,
            tokens=None, embeds=None, frames=None, positions=None,
            cache=None, remat: bool = False, q_lens=None,
            last_only: bool = False, logit_rows=None,
            expert_stats: bool = False) -> Output:
    """Unified forward.

    tokens  (b, s_text) int32 — text token ids (None for pure-embed input)
    embeds  (b, s_front, h)   — vlm patch-embedding stub, prepended to tokens
    frames  (b, n_frames, d)  — audio frame-embedding stub (whisper encoder)
    cache   from ``init_cache`` (prefill fills it, decode reads+updates)
    q_lens  (b,) int32        — unified mixed prefill/decode step: slot i
                                contributes the first q_lens[i] of its s
                                token rows (a prefill chunk, one decode
                                token, or 0 = idle) at its own cache offset
                                (``cache["length"]`` must be the per-slot
                                vector); the cache advances by q_lens per
                                slot and rows past q_lens[i] are inert
                                padding whose logits/cache writes are
                                masked.  Requires a cache; attention-cached
                                families only (dense/vlm-text, moe, mla).
    expert_stats                accumulate per-expert routed-slot counts
                                across MoE layers into
                                ``Output.expert_counts`` (the serving
                                tier's expert-load-skew observability);
                                None on the output otherwise.
    last_only                   with q_lens: apply the LM head only to each
                                slot's last valid row (position
                                q_lens[i] - 1), returning (b, 1, v) logits —
                                the serving hot path, which would otherwise
                                pay the vocab matmul on every pad row of the
                                (b, chunk) buffer.
    logit_rows  (b, n) int32    with last_only: override the per-slot row
                                selection — the LM head is applied to these
                                row indices instead of just q_lens[i] - 1,
                                returning (b, n, v) logits.  Speculative
                                verify extracts one logit row per draft
                                position this way without paying the vocab
                                matmul on the padding rows.
    """
    if q_lens is not None and cache is None:
        raise ValueError("q_lens (unified mixed step) requires a cache")
    if last_only and q_lens is None:
        raise ValueError("last_only requires q_lens (the unified mixed step)")
    if logit_rows is not None and not last_only:
        raise ValueError("logit_rows requires last_only (it overrides the "
                         "per-slot head-row selection)")
    length = None if cache is None else cache["length"]
    block_tables = None if cache is None else cache.get("block_tables")
    if block_tables is not None and q_lens is None:
        raise ValueError("a paged cache (block_tables) requires the unified "
                         "mixed step (q_lens)")
    idx = 0 if cache is None else length

    parts = []
    if embeds is not None:
        parts.append(embeds.astype(jnp.bfloat16)
                     if cfg.dtype == "bfloat16" else embeds)
    if tokens is not None:
        t_emb = jnp.take(params["embed"], tokens, axis=0)
        if cfg.tie_embeddings:
            t_emb = t_emb * math.sqrt(cfg.d_model)
        parts.append(t_emb)
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    b, s, h = x.shape
    x = plan.constrain(x, "batch", "seq", "embed")

    if positions is None:
        base = jnp.atleast_2d(L.positions_from(idx, s))
        positions = (jnp.broadcast_to(base[:, None], (b, 3, s))
                     if cfg.mrope else base)

    enc_out = None
    if cfg.family == "audio" and frames is not None:
        enc_out = encode_audio(params, frames, cfg, plan)

    aux_total = jnp.zeros((), jnp.float32)
    counts_total = jnp.zeros((max(cfg.n_experts, 1),), jnp.int32) \
        if expert_stats else None
    new_groups = []
    for gi, g in enumerate(layer_plan(cfg)):
        p_g = params["groups"][gi]
        c_g = None if cache is None else cache["groups"][gi]
        # the expert GEMM kernel reads each layer's experts out of the
        # stack: the scan closes over the stacks (loop-invariant, never
        # sliced) and carries the layer index in their place
        stack = {}
        if g.kind == "moe" and not remat and MOE.reads_stack(plan):
            moe_p = dict(p_g["moe"])
            stack = {k: moe_p.pop(k) for k in MOE.EXPERT_WEIGHTS
                     if k in moe_p}
            moe_p["layer"] = jnp.arange(g.count, dtype=jnp.int32)
            p_g = {**p_g, "moe": moe_p}

        def body(carry, xs, _g=g, _stack=stack):
            if expert_stats:
                x, aux, cnt = carry
            else:
                x, aux = carry
                cnt = None
            p_l, c_l = xs
            if _stack:
                p_l = {**p_l, "moe": {**p_l["moe"], **_stack}}

            def apply(kind, pp, xx, cc):
                r = apply_sublayer(kind, pp, xx, cc, cfg=cfg, plan=plan,
                                   positions=positions, length=length,
                                   enc_out=enc_out, q_lens=q_lens,
                                   block_tables=block_tables,
                                   with_stats=expert_stats)
                return r if expert_stats else r + (None,)

            if _g.kind == "pattern":
                new_c_l = {}
                for i, k in enumerate(_g.sub):
                    ci = None if c_l is None else c_l[f"l{i}"]
                    x, nc, a, c_ = apply(k, p_l[f"l{i}"], x, ci)
                    aux = aux + a
                    if cnt is not None:
                        cnt = cnt + c_
                    if nc is not None:
                        new_c_l[f"l{i}"] = nc
                new_c_l = new_c_l or None
            else:
                x, new_c_l, a, c_ = apply(_g.kind, p_l, x, c_l)
                aux = aux + a
                if cnt is not None:
                    cnt = cnt + c_
            # Megatron-style sequence parallelism on the residual stream:
            # the scan carry (saved for backward, x n_layers) lives
            # seq-sharded over the TP axis instead of replicated.
            x = plan.constrain(x, "batch", "seq_resid", "embed")
            carry = (x, aux, cnt) if expert_stats else (x, aux)
            return carry, new_c_l

        if remat:
            body = jax.checkpoint(body)
        if expert_stats:
            (x, aux_total, counts_total), new_c_g = jax.lax.scan(
                body, (x, aux_total, counts_total), (p_g, c_g))
        else:
            (x, aux_total), new_c_g = jax.lax.scan(
                body, (x, aux_total), (p_g, c_g))
        new_groups.append(new_c_g)

    if last_only:   # per-slot last valid row(s); norm/head are per-token ops
        rows = (jnp.maximum(q_lens - 1, 0)[:, None] if logit_rows is None
                else logit_rows)
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsh,hv->bsv", x, head)
    if cfg.padded_vocab != cfg.vocab_size:   # mask padding columns
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, cfg.padded_vocab), 2)
        logits = jnp.where(col < cfg.vocab_size, logits, L.NEG_INF)
    logits = plan.constrain(logits, "batch", "seq", "vocab")

    new_cache = None
    if cache is not None:
        adv = s if q_lens is None else q_lens     # per-slot ragged advance
        new_cache = {"groups": new_groups, "length": length + adv}
        if block_tables is not None:    # host-owned mapping rides through
            new_cache["block_tables"] = block_tables
    return Output(logits=logits, cache=new_cache, aux=aux_total,
                  expert_counts=counts_total)


__all__ = ["Group", "layer_plan", "model_spec", "init_params",
           "abstract_params", "param_axes", "count_params", "init_cache",
           "init_paged_cache", "cache_axes", "forward", "Output",
           "encode_audio", "apply_sublayer", "sublayer_spec"]
