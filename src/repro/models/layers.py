"""Transformer layer substrate: norms, RoPE/M-RoPE, attention variants, MLPs.

Attention is implemented with a chunked online-softmax scan over KV blocks
(flash-attention structure in pure JAX) so that prefill at 32k lowers with
bounded live memory; the Pallas ``flash_chunk`` kernel in ``repro.kernels``
is the TPU-optimized version of the cache-backed path (ragged mixed
chunks, uniform chunked prefill, and — as its sq == 1 specialization
``flash_decode`` — single-token decode); see docs/kernels.md.

All parameter declarations carry logical axes consumed by the partitioner:
  "heads"/"kv_heads"/"ffn"/"vocab" shard over the TP ("model") mesh axis,
  "batch" over the DP axes, "expert" over the EP axis (see moe.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.partitioner import NULL_PLAN, ShardingPlan
from repro.kernels.policy import KernelPolicy
from repro.models.param import P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + weight.astype(jnp.float32))
            ).astype(dtype)


def activate(x_gate, x_up, kind: str):
    if kind == "swiglu":
        return jax.nn.silu(x_gate) * x_up
    if kind == "geglu":
        return jax.nn.gelu(x_gate, approximate=True) * x_up
    return jax.nn.gelu(x_gate, approximate=True)  # plain gelu: x_up unused


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., s, n, hd); positions: broadcastable to (..., s)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., s, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE: three position streams (t, h, w) drive
    disjoint frequency sections.  x: (b, s, n, hd); positions3: (b, 3, s);
    sections: per-stream pair counts summing to hd//2."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    # section id of every frequency pair -> which position stream drives it
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=hd // 2)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, :, None],
                         (x.shape[0], hd // 2, positions3.shape[-1])),
        axis=1)                          # (b, hd/2, s)
    ang = jnp.einsum("bfs,f->bsf", pos, inv)      # (b, s, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (flash structure, pure JAX)
# ---------------------------------------------------------------------------

_SCORE_BLOCK_BUDGET = 256 * 1024 * 1024   # f32 score-block bytes per q-step


def _pick_q_block(b: int, nq: int, sq: int, skv: int) -> int:
    """Largest power-of-2 q-block whose (b, nq, qb, skv) f32 score tensor
    stays under the budget (>= 8)."""
    qb = 8
    while qb * 2 <= sq and b * nq * (qb * 2) * skv * 4 <= _SCORE_BLOCK_BUDGET:
        qb *= 2
    return qb


def chunked_attention(q, k, v, *, q_offset=0, kv_len: Optional[jax.Array] = None,
                      causal: bool = True, window: int = 0,
                      chunk_size: Optional[int] = None,
                      scale: Optional[float] = None,
                      k_positions: Optional[jax.Array] = None,
                      policy: Optional[KernelPolicy] = None):
    """q: (b, sq, nq, hd); k, v: (b, skv, nkv, hd[v]).  GQA via head groups.

    Blocked over the QUERY axis: an outer ``lax.scan`` walks q blocks with no
    carry (ys only), so the backward pass recomputes per-block rather than
    saving running-softmax carries — this is what lets train_4k/prefill_32k
    fit.  Each step materializes one (b, nkv, g, qb, skv) f32 score block,
    with qb auto-sized to a fixed VMEM/HBM budget (or forced via chunk_size).

    ``q_offset`` and ``kv_len`` may be scalars (uniform batch — train /
    single-request prefill) or (b,) vectors (the unified mixed
    prefill/decode serving step, where every slot sits at its own cache
    offset).  A slot whose queries are all masked (a ragged tail / idle
    slot) yields finite garbage rows — callers discard them.  ``kv_len``
    masks the cache tail, ``window`` applies a sliding-window mask,
    ``k_positions`` ((skv,) or (b, skv)) gives explicit absolute KV
    positions for ring-buffer caches (negative = invalid).

    ``policy.flash_chunk`` routes the cache-backed causal case through the
    Pallas ragged mixed-chunk kernel (``repro.kernels.flash_chunk``): the
    per-slot query count is ``kv_len - q_offset`` (how the unified step and
    the uniform chunked prefill both encode it), KV tiles beyond a slot's
    causal frontier are skipped, and ragged-tail rows come back as exact
    zeros instead of garbage.  Sliding windows and explicit ``k_positions``
    (ring buffers) and the stateless train path (``kv_len is None``, which
    needs autodiff through the scan) keep this jnp body.
    """
    b, sq, nq, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    groups = nq // nkv
    scale = scale if scale is not None else hd ** -0.5

    if (policy is not None and policy.flash_chunk and causal
            and window == 0 and k_positions is None and kv_len is not None):
        from repro.kernels import ops as _kops
        off = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(q_offset, jnp.int32)), (b,))
        lens = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(kv_len, jnp.int32)), (b,))
        return _kops.flash_chunk(q, k, v, off, lens - off, lens,
                                 scale=float(scale))
    qb = chunk_size or _pick_q_block(b, nq, sq, skv)
    qb = min(qb, sq)
    n_blocks = -(-sq // qb)
    pad = n_blocks * qb - sq
    qg = (q * scale).reshape(b, sq, nkv, groups, hd)
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qc = qg.reshape(b, n_blocks, qb, nkv, groups, hd).transpose(1, 0, 2, 3, 4, 5)

    # (b_, 1) per-slot query offsets; b_ is 1 (scalar, broadcasts) or b
    q_off = jnp.atleast_1d(jnp.asarray(q_offset, jnp.int32))[:, None]
    if k_positions is None:
        k_pos = jnp.arange(skv)[None]                      # (1, skv)
        base_mask = (jnp.ones((1, skv), bool) if kv_len is None
                     else k_pos < jnp.atleast_1d(jnp.asarray(kv_len))[:, None])
    else:
        k_pos = jnp.atleast_2d(k_positions)                # (1|b, skv)
        base_mask = k_pos >= 0

    def step(_, inp):
        idx, q_blk = inp                       # q_blk: (b, qb, nkv, g, hd)
        q_pos = q_off + idx * qb + jnp.arange(qb)          # (b_, qb)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k,
                       preferred_element_type=jnp.float32)
        mask = jnp.broadcast_to(base_mask[:, None, :],
                                (base_mask.shape[0], qb, skv))
        if causal:
            mask = mask & (k_pos[:, None, :] <= q_pos[..., None])
        if window > 0:
            mask = mask & (k_pos[:, None, :] > q_pos[..., None] - window)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        out_blk = pv / jnp.maximum(l, 1e-20)[..., None]
        return (), out_blk.astype(q.dtype)     # (b, nkv, g, qb, hdv)

    step = jax.checkpoint(step, prevent_cse=False)
    _, outs = jax.lax.scan(step, (), (jnp.arange(n_blocks), qc))
    # (nb, b, nkv, g, qb, hdv) -> (b, nb*qb, nq, hdv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, n_blocks * qb, nq, hdv)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# Decode attention (single new token against a long cache)
# ---------------------------------------------------------------------------

def positions_from(idx, s: int):
    """(s,) or (b, s) absolute positions from a scalar or (b,) offset."""
    idx = jnp.asarray(idx)
    if idx.ndim:
        return idx[:, None] + jnp.arange(s)
    return jnp.arange(s) + idx


def write_cache(buf, new, idx, valid_len=None):
    """Write ``new`` (b, s, ...) into ``buf`` (b, S, ...) at offset ``idx``.

    scalar idx  -> dynamic_update_slice (uniform batch — train/prefill)
    (b,) idx    -> per-slot write at per-slot offsets (continuous batching);
                   ``valid_len`` (b,) keeps only the first valid_len[i] rows
                   of slot i — the ragged-tail mask of the unified mixed
                   prefill/decode step (rows past it are dropped, so an idle
                   or short-chunk slot never touches its cache)
    """
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, idx, axis=1)
    b, skv = buf.shape[:2]
    s = new.shape[1]
    if s == 1 and valid_len is None:   # classic decode: one masked write
        m = jnp.arange(skv)[None] == idx[:, None]          # (b, S)
        m = m.reshape(b, skv, *([1] * (buf.ndim - 2)))
        return jnp.where(m, new.astype(buf.dtype), buf)
    rows = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None]   # (b, s)
    if valid_len is not None:
        rows = jnp.where(jnp.arange(s)[None] < valid_len[:, None], rows, skv)
    bi = jnp.arange(b)[:, None]
    # invalid rows were parked at skv (out of bounds) -> dropped by scatter
    return buf.at[bi, rows].set(new.astype(buf.dtype), mode="drop")


def write_cache_paged(pool, new, idx, block_table, valid_len=None):
    """Write ``new`` (b, s, ...) into the paged pool (P, page, ...) at the
    slots' logical offsets ``idx`` (b,), routed through ``block_table``
    (b, max_blocks) — logical block ``pos // page`` maps to a physical page.

    Same ragged-tail contract as ``write_cache``: rows past ``valid_len[i]``
    (and rows whose logical block is unallocated, table entry < 0) are
    parked out of bounds and dropped by the scatter, so an idle slot — or a
    slot whose pages the host allocator withheld — never touches the pool.
    Shared prefix pages are never written either: the host hands a slot a
    cache offset past its shared full blocks, so ``pos`` starts beyond them.
    """
    P, ps = pool.shape[0], pool.shape[1]
    b, s = new.shape[:2]
    nb = block_table.shape[1]
    pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None]    # (b, s)
    blk = jnp.clip(pos // ps, 0, nb - 1)
    page = jnp.take_along_axis(block_table, blk, axis=1)         # (b, s)
    ok = page >= 0
    if valid_len is not None:
        ok = ok & (jnp.arange(s)[None] < valid_len[:, None])
    page = jnp.where(ok, page, P)      # park invalid rows out of bounds
    return pool.at[page, pos % ps].set(new.astype(pool.dtype), mode="drop")


def gather_pages(pool, block_table):
    """Materialize a slot-major dense view (b, max_blocks*page, ...) of the
    paged pool through the block tables.  ``max_blocks * page == max_len``,
    so the view is shape-identical to the dense cache buffer — unallocated
    blocks (table entry < 0) read page 0's garbage, which sits at logical
    positions >= the slot's length and is masked by ``kv_len`` exactly like
    a dense buffer's stale tail.  The jnp fallback of the paged attention
    path is therefore *the same computation* as the dense path."""
    P, ps = pool.shape[0], pool.shape[1]
    b, nb = block_table.shape
    pages = jnp.take(pool, jnp.clip(block_table, 0, P - 1), axis=0)
    return pages.reshape(b, nb * ps, *pool.shape[2:])


def paged_flash_attention(plan: ShardingPlan, q, k_pool, v_pool, block_table,
                          q_offset, q_len, kv_len):
    """The ``flash_chunk_paged`` kernel, one call per device on a mesh.

    GSPMD cannot partition a Mosaic kernel, so on a mesh the call runs under
    ``shard_map``: each device takes its batch slots (the plan's "batch"
    axes) and its group of heads (the "heads" axes, when the kv heads split
    the same way, or there is one latent kv head).  Pools stay whole on the
    batch axes, because any slot may own any page."""
    from repro.kernels import ops as _kops
    scale = float(q.shape[-1] ** -0.5)
    if not plan.enabled:
        return _kops.flash_chunk_paged(q, k_pool, v_pool, block_table,
                                       q_offset, q_len, kv_len, scale=scale)
    b, _, nq, _ = q.shape
    nkv = k_pool.shape[2]
    bspec = plan.spec_for_shape((b,), ("batch",))
    b_ax = bspec[0] if len(bspec) else None
    hspec = plan.spec_for_shape((nq,), ("heads",))
    h_ax = hspec[0] if len(hspec) else None
    tp = plan.axis_size(h_ax if isinstance(h_ax, tuple) else
                        ((h_ax,) if h_ax else ()))
    if nkv > 1 and nkv % tp:
        h_ax = None                  # kv heads cannot follow: keep all heads
    kv_ax = h_ax if nkv > 1 else None
    S = PartitionSpec
    fn = functools.partial(_kops.flash_chunk_paged, scale=scale)
    return jax.shard_map(
        fn, mesh=plan.mesh,
        in_specs=(S(b_ax, None, h_ax, None), S(None, None, kv_ax, None),
                  S(None, None, kv_ax, None), S(b_ax, None), S(b_ax),
                  S(b_ax), S(b_ax)),
        out_specs=S(b_ax, None, h_ax, None), check_vma=False,
    )(q, k_pool, v_pool, block_table, q_offset, q_len, kv_len)


def decode_attention(q, k, v, *, kv_len=None, q_positions=None, window: int = 0,
                     k_positions: Optional[jax.Array] = None,
                     scale: Optional[float] = None,
                     policy: Optional[KernelPolicy] = None):
    """One-step attention: q (b, sq<=2, nq, hd) vs cache k/v (b, S, nkv, hd[v]).

    No chunk scan — the score tensor (b, nkv, g, sq, S) is materialized so
    that a *seq-sharded* cache (kv_seq over the TP mesh axis) keeps the whole
    computation local-per-shard with XLA inserting only the softmax-reduction
    collectives (flash-decode structure under GSPMD).

    ``kv_len`` (scalar or (b,)) masks slots >= length; ``k_positions`` ((S,) or
    (b, S)) gives explicit absolute positions for ring-buffer caches
    (negative = invalid) and replaces the slot index in causal/window tests.
    ``q_positions``: (sq,) or (b, sq) absolute positions of the queries.

    ``policy.flash_decode`` routes the standard decode case (sq == 1, no
    window, slot-indexed cache) through the Pallas online-softmax kernel
    (repro.kernels.flash_decode), whose length mask assumes the decode
    invariant q_position == kv_len - 1 — exactly what the model/engine pass
    here.  Other cases (ring buffers, windows, sq == 2) keep the jnp body.
    """
    b, sq, nq, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else hd ** -0.5

    if (policy is not None and policy.flash_decode and sq == 1
            and window == 0 and k_positions is None and kv_len is not None):
        from repro.kernels import ops as _kops
        lens = jnp.broadcast_to(jnp.atleast_1d(kv_len), (b,)).astype(jnp.int32)
        # kv_len == 0 (idle slots of a unified mixed step) is masked
        # natively by the kernel — those rows come back as exact zeros and
        # callers discard them.
        return _kops.flash_decode(q[:, 0], k, v, lens,
                                  scale=float(scale))[:, None]
    if q_positions is None:
        q_positions = jnp.zeros((sq,), jnp.int32)
    q_pos = jnp.broadcast_to(jnp.atleast_2d(q_positions), (b, sq))
    qg = (q * scale).reshape(b, sq, nkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    if k_positions is None:
        k_pos = jnp.broadcast_to(jnp.arange(skv)[None], (b, skv))
        if kv_len is None:
            valid = jnp.ones((b, skv), bool)
        else:
            valid = k_pos < jnp.broadcast_to(jnp.atleast_1d(kv_len),
                                             (b,))[:, None]
    else:
        k_pos = jnp.broadcast_to(jnp.atleast_2d(k_positions), (b, skv))
        valid = k_pos >= 0
    mask = valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window > 0:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)      # (b,nkv,g,sq,skv)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nq, v.shape[-1]) \
        .astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA / MQA attention block
# ---------------------------------------------------------------------------

def gqa_spec(cfg: ModelConfig) -> dict:
    h, nq, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        # (heads, head_dim) fallback chain: when the head count does not
        # divide the TP axis (smollm 15q, phi 8kv, gemma 1kv) the partitioner
        # shards head_dim instead — see ShardingPlan.spec_for_shape.
        "wq": P((h, nq, hd), ("embed", "heads", "head_dim")),
        "wk": P((h, nkv, hd), ("embed", "kv_heads", "kv_head_dim")),
        "wv": P((h, nkv, hd), ("embed", "kv_heads", "kv_head_dim")),
        "wo": P((nq, hd, h), ("heads", "head_dim", "embed")),
        "norm": P((h,), ("embed",), init="zeros"),
    }


@dataclasses.dataclass
class KVView:
    """Either fresh K/V (prefill/train) or a cache to read+update (decode).

    With ``block_table`` set, k/v are paged POOLS (P, page, nkv, hd) and the
    table (b, max_blocks) maps each slot's logical blocks to physical pages
    (docs/kv_cache.md); without it they are dense (b, max_len, nkv, hd)
    per-slot buffers."""
    k: jax.Array
    v: jax.Array
    length: Optional[jax.Array] = None  # valid prefix length of the cache
    block_table: Optional[jax.Array] = None


def gqa_attention(p, x, cfg: ModelConfig, plan: ShardingPlan = NULL_PLAN, *,
                  positions=None, cache: Optional[KVView] = None,
                  window: int = 0, chunk_size: int = 1024, q_lens=None):
    """Returns (out, new_cache_kv).  x: (b, s, h).

    Four modes:
      cache is None                 train / stateless prefill (fresh K/V)
      cache given, s > 1            prefill INTO a preallocated cache buffer
      cache given, s == 1           decode — single token vs the cache, via
                                    ``decode_attention`` (seq-sharded friendly)
      cache given, q_lens (b,)      unified mixed step — slot i contributes
                                    the first q_lens[i] of its s rows
                                    (prefill chunk, single decode token, or
                                    0 = idle); cache.length is the per-slot
                                    offset vector and ragged tails are
                                    masked out of both writes and scores
    """
    b, s, h = x.shape
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsh,hnd->bsnd", xn, p["wq"])
    k = jnp.einsum("bsh,hnd->bsnd", xn, p["wk"])
    v = jnp.einsum("bsh,hnd->bsnd", xn, p["wv"])
    if cache is None or s > 1:
        q = plan.constrain(q, "batch", "seq", "heads", None)
        k = plan.constrain(k, "batch", "seq", "kv_heads", None)

    idx = 0 if cache is None else cache.length
    if positions is None:
        positions = jnp.atleast_2d(positions_from(idx, s))
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = chunked_attention(q, k, v, causal=True, window=window,
                                chunk_size=chunk_size)
        new_kv = (k, v)
    elif cache.block_table is not None:   # paged cache (pool + block table)
        if q_lens is None:
            raise NotImplementedError(
                "paged KV cache requires the unified mixed step (q_lens)")
        if window:
            raise NotImplementedError("paged KV cache with sliding window")
        bt = cache.block_table
        kc = write_cache_paged(cache.k, k, idx, bt, valid_len=q_lens)
        vc = write_cache_paged(cache.v, v, idx, bt, valid_len=q_lens)
        # no plan.constrain on the pools: they carry no batch axis and stay
        # replicated for now (seq-sharding a page pool is a follow-up)
        kv_len = idx + q_lens
        pol = plan.kernels
        if pol is not None and pol.flash_chunk:
            out = paged_flash_attention(plan, q, kc, vc, bt, idx, q_lens,
                                        kv_len)
        else:
            # gathered view is shape-identical to the dense buffer, so the
            # jnp fallback routes EXACTLY like the dense path (bit-identical
            # streams — the engine-level paged-vs-dense oracle relies on it)
            kd, vd = gather_pages(kc, bt), gather_pages(vc, bt)
            if s == 1:
                out = decode_attention(q, kd, vd, kv_len=kv_len,
                                       q_positions=positions_from(idx, s),
                                       policy=pol)
            else:
                out = chunked_attention(q, kd, vd, q_offset=idx,
                                        kv_len=kv_len, causal=True,
                                        chunk_size=chunk_size, policy=pol)
        new_kv = (kc, vc)
    else:
        kc = write_cache(cache.k, k, idx, valid_len=q_lens)
        vc = write_cache(cache.v, v, idx, valid_len=q_lens)
        kc = plan.constrain(kc, "batch", "kv_seq", None, None)
        vc = plan.constrain(vc, "batch", "kv_seq", None, None)
        if q_lens is not None:   # unified mixed step (per-slot ragged batch)
            if s == 1:           # decode-shaped budget: flash_decode eligible
                out = decode_attention(q, kc, vc, kv_len=idx + q_lens,
                                       q_positions=positions_from(idx, s),
                                       window=window, policy=plan.kernels)
            else:
                out = chunked_attention(q, kc, vc, q_offset=idx,
                                        kv_len=idx + q_lens, causal=True,
                                        window=window, chunk_size=chunk_size,
                                        policy=plan.kernels)
        elif s == 1:
            out = decode_attention(q, kc, vc, kv_len=idx + s,
                                   q_positions=positions_from(idx, s),
                                   window=window, policy=plan.kernels)
        else:  # prefill into the buffer (uniform batch, scalar idx)
            out = chunked_attention(q, kc, vc, q_offset=idx, kv_len=idx + s,
                                    causal=True, window=window,
                                    chunk_size=chunk_size,
                                    policy=plan.kernels)
        new_kv = (kc, vc)
    out = jnp.einsum("bsnd,ndh->bsh", out, p["wo"])
    return plan.constrain(out, "batch", "seq_resid", "embed"), new_kv


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention) — DeepSeek-V2 / MiniCPM3
# ---------------------------------------------------------------------------

def mla_spec(cfg: ModelConfig) -> dict:
    h, nh, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, qr, rd, vd = (cfg.kv_lora_rank, cfg.q_lora_rank or cfg.d_model,
                     cfg.rope_head_dim, cfg.v_head_dim)
    return {
        "w_dq": P((h, qr), ("embed", "qk")),
        "q_norm": P((qr,), ("qk",), init="zeros"),
        "w_uq": P((qr, nh, hd + rd), ("qk", "heads", "head_dim")),
        "w_dkv": P((h, r), ("embed", "qk")),
        "kv_norm": P((r,), ("qk",), init="zeros"),
        "w_kr": P((h, rd), ("embed", None)),
        "w_uk": P((r, nh, hd), ("qk", "heads", "head_dim")),
        "w_uv": P((r, nh, vd), ("qk", "heads", "head_dim")),
        "wo": P((nh, vd, h), ("heads", "head_dim", "embed")),
        "norm": P((h,), ("embed",), init="zeros"),
    }


def _mla_qkr(p, x, cfg, positions):
    """Shared query path + rope'd latent key."""
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    ql = rms_norm(xn @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rnd->bsnd", ql, p["w_uq"])
    q_nope, q_rope = jnp.split(q, [cfg.head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c = rms_norm(xn @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)   # (b, s, r)
    k_rope = apply_rope((xn @ p["w_kr"])[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]                 # (b, s, rd)
    return q_nope, q_rope, c, k_rope


def mla_attention(p, x, cfg: ModelConfig, plan: ShardingPlan = NULL_PLAN, *,
                  positions=None, cache=None, chunk_size: Optional[int] = None,
                  absorb: Optional[bool] = None, q_lens=None,
                  block_table=None):
    """MLA attention.  cache = (c_cache, kr_cache, length) for decode.

    ``block_table`` (b, max_blocks) marks a paged latent cache: c/kr are
    pools (P, page, r|rd) and attention reads them through a gathered
    slot-major view (the latent is rank-r compressed, so the gather is
    cheap — both MLA regimes materialize latent-sized tensors anyway; the
    dedicated paged kernel is reserved for the GQA path).

    ``absorb=None`` auto-selects the regime (the DeepSeek serving recipe):
      s > 1 (train / prefill)  expanded — K/V up-projected per head, standard
                               attention; scores cost nh*(hd+rd) per pair.
      s == 1 (decode)          absorbed — scores/values live in the rank-r
                               latent space, so the per-step cache read is
                               (r + rd) per token instead of 2*nh*hd.
    Using absorbed at s >> 1 would multiply score FLOPs/bytes by ~r/hd (4x
    for deepseek-v2) — that blowup is exactly what the auto rule avoids.

    ``q_lens`` (b,) marks the unified mixed serving step: slot i contributes
    its first q_lens[i] rows (cache length is the per-slot offset vector);
    ragged tails are masked from cache writes and the kv mask.
    """
    b, s, h = x.shape
    nh, hd, vd, r = cfg.n_heads, cfg.head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if absorb is None:
        absorb = (cache is not None and s == 1)
    if positions is None:
        off = 0 if cache is None else cache[2]
        positions = jnp.atleast_2d(positions_from(off, s))
    q_nope, q_rope, c, k_rope = _mla_qkr(p, x, cfg, positions)
    q_nope = plan.constrain(q_nope, "batch", "seq", "heads", None)

    if not absorb:
        # training/prefill: expand K,V per head and run standard attention.
        # With a cache (chunked prefill) the expansion reads the FULL latent
        # buffer so chunk i attends to chunks 0..i (DeepSeek's recipe:
        # recompute per-head K/V from the latent cache).
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if cache is not None and block_table is not None:   # paged latent
            if q_lens is None:
                raise NotImplementedError(
                    "paged KV cache requires the unified mixed step (q_lens)")
            c_cache, kr_cache, idx = cache
            cc = write_cache_paged(c_cache, c, idx, block_table,
                                   valid_len=q_lens)
            krc = write_cache_paged(kr_cache, k_rope, idx, block_table,
                                    valid_len=q_lens)
            src_c = gather_pages(cc, block_table)
            src_kr = gather_pages(krc, block_table)
            skv = src_c.shape[1]
            off = idx
            kv_len = idx + q_lens
            new_cache = (cc, krc)
        elif cache is not None:
            c_cache, kr_cache, idx = cache
            cc = write_cache(c_cache, c, idx, valid_len=q_lens)
            krc = write_cache(kr_cache, k_rope, idx, valid_len=q_lens)
            cc = plan.constrain(cc, "batch", "kv_seq", None)
            krc = plan.constrain(krc, "batch", "kv_seq", None)
            src_c, src_kr, skv = cc, krc, cc.shape[1]
            off = idx
            kv_len = idx + (s if q_lens is None else q_lens)
            new_cache = (cc, krc)
        else:
            src_c, src_kr, skv = c, k_rope, s
            off, kv_len = 0, None
            new_cache = (c, k_rope)
        k_nope = jnp.einsum("bsr,rnd->bsnd", src_c, p["w_uk"])
        v = jnp.einsum("bsr,rnd->bsnd", src_c, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(src_kr[:, :, None],
                                      (b, skv, nh, cfg.rope_head_dim))],
            axis=-1)
        # explicit head sharding: without these GSPMD's backward guesses
        # reshard q/k/v to full-head replicas ("involuntary full
        # rematerialization" warnings, f32 full copies in the HLO)
        q = plan.constrain(q, "batch", "seq", "heads", None)
        k = plan.constrain(k, "batch", "seq", "heads", None)
        v = plan.constrain(v, "batch", "seq", "heads", None)
        # kv_len is None on the stateless train path, so the policy routing
        # only fires for cache-backed (chunked / mixed) prefill
        out = chunked_attention(q, k, v, q_offset=off, kv_len=kv_len,
                                causal=True, chunk_size=chunk_size,
                                scale=(hd + cfg.rope_head_dim) ** -0.5,
                                policy=plan.kernels)
    else:
        # absorbed attention: fold w_uk into q, w_uv into the output
        q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, p["w_uk"])  # (b,s,nh,r)
        if cache is None:
            cc, krc, off, kv_len = c, k_rope, 0, None
            new_cache = (c, k_rope)
        elif block_table is not None:    # paged latent cache
            if q_lens is None:
                raise NotImplementedError(
                    "paged KV cache requires the unified mixed step (q_lens)")
            c_cache, kr_cache, idx = cache
            cc_pool = write_cache_paged(c_cache, c, idx, block_table,
                                        valid_len=q_lens)
            krc_pool = write_cache_paged(kr_cache, k_rope, idx, block_table,
                                         valid_len=q_lens)
            cc = gather_pages(cc_pool, block_table)
            krc = gather_pages(krc_pool, block_table)
            off = idx
            kv_len = idx + q_lens
            new_cache = (cc_pool, krc_pool)
        else:
            c_cache, kr_cache, idx = cache
            cc = write_cache(c_cache, c, idx, valid_len=q_lens)
            krc = write_cache(kr_cache, k_rope, idx, valid_len=q_lens)
            cc = plan.constrain(cc, "batch", "kv_seq", None)
            krc = plan.constrain(krc, "batch", "kv_seq", None)
            off = idx
            kv_len = idx + (s if q_lens is None else q_lens)
            new_cache = (cc, krc)
        # latent "keys" = [c ; k_rope], latent "values" = c (single kv head)
        k_lat = jnp.concatenate([cc, krc], axis=-1)[:, :, None, :]
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)       # (b,s,nh,r+rd)
        if cache is not None and s == 1:                         # decode
            o_lat = decode_attention(
                q_full, k_lat, cc[:, :, None, :], kv_len=kv_len,
                q_positions=positions_from(off, s),
                scale=(hd + cfg.rope_head_dim) ** -0.5,
                policy=plan.kernels)                             # (b,s,nh,r)
        else:
            o_lat = chunked_attention(
                q_full, k_lat, cc[:, :, None, :], q_offset=off, kv_len=kv_len,
                causal=True, chunk_size=chunk_size,
                scale=(hd + cfg.rope_head_dim) ** -0.5,
                policy=plan.kernels)                             # (b,s,nh,r)
        out = jnp.einsum("bsnr,rnd->bsnd", o_lat, p["w_uv"])
    out = jnp.einsum("bsnd,ndh->bsh", out, p["wo"])
    return plan.constrain(out, "batch", "seq_resid", "embed"), new_cache


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_spec(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    h, f = cfg.d_model, d_ff or cfg.d_ff
    gated = cfg.activation in ("swiglu", "geglu")
    spec = {
        "w_in": P((h, f), ("embed", "ffn")),
        "w_out": P((f, h), ("ffn", "embed")),
        "norm": P((h,), ("embed",), init="zeros"),
    }
    if gated:
        spec["w_gate"] = P((h, f), ("embed", "ffn"))
    return spec


def mlp(p, x, cfg: ModelConfig, plan: ShardingPlan = NULL_PLAN):
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    up = xn @ p["w_in"]
    gate = xn @ p["w_gate"] if "w_gate" in p else up
    hmid = activate(gate, up, cfg.activation)
    hmid = plan.constrain(hmid, "batch", "seq", "ffn")
    out = hmid @ p["w_out"]
    return plan.constrain(out, "batch", "seq_resid", "embed")


__all__ = [
    "rms_norm", "activate", "apply_rope", "apply_mrope", "chunked_attention",
    "gqa_spec", "gqa_attention", "mla_spec", "mla_attention",
    "mlp_spec", "mlp", "KVView", "NEG_INF",
    "write_cache", "write_cache_paged", "gather_pages",
]
