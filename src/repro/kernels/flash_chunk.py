"""Ragged mixed-chunk flash attention (Pallas TPU) — the unified-step kernel.

One kernel family serves every slot shape of the unified mixed
prefill/decode serving iteration: slot ``i`` contributes ``q_len[i]`` query
rows (a prefill chunk, one decode token, or 0 = idle) at absolute cache
offset ``q_offset[i]`` against a cache whose valid prefix is ``kv_len[i]``.

Grid ``(batch, q-tile, S-tile)``; the S dimension is the innermost
sequential axis so the online-softmax state (m, l, acc) lives in VMEM
scratch across KV tiles (same schedule as the old ``flash_decode``, which
is now the ``sq == 1`` specialization of this kernel — see
``flash_decode.py``).  Blocks are token-major and hold EVERY kv head:
q ``(bq, nq, hd)``, k/v ``(bs, nkv, hd)``.  Their last two dims are then the
array's own, which is what the TPU tiling rule accepts for any head count
(a one-head ``(bs, 1, hd)`` block of an 8-head cache is refused), so the
cache keeps its ``(…, S|page, nkv, hd)`` layout and the kernel walks the kv
heads in an unrolled loop, each against its ``g = nq // nkv`` query heads.  Per-slot ``q_offset``/``q_len``/``kv_len`` arrive
via scalar prefetch, so raggedness is handled at *tile* granularity:

  * KV tiles past a slot's causal frontier (``q_offset + q_tile_hi``) or
    past its ``kv_len`` are skipped entirely — a ``pl.when`` gates the MXU
    work and the k/v BlockSpec index maps clamp to the frontier tile, so
    the pipeline re-uses the resident block instead of streaming dead
    cache lines from HBM;
  * q tiles past ``q_len`` (the ragged tail) and idle slots
    (``q_len == 0``) do zero compute — their output rows are written as
    exact zeros (finite; callers never read them);
  * ``kv_len == 0`` is masked natively: no caller-side length floor needed.

Causal masking inside a live tile is per element: query row ``r`` of slot
``i`` sees keys ``pos <= q_offset[i] + r`` and ``pos < kv_len[i]``.  GQA
runs ``g = nq // nkv`` query groups per kv head; MLA-absorbed decode is the
``nkv == 1`` case with ``hdv != hd`` (latent keys carry the decoupled-rope
dims, values are the bare latent).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_chunk_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *,
                        bq: int, bs: int, g: int, nkv: int, scale: float):
    bi, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_off = meta_ref[0, bi]
    q_len = meta_ref[1, bi]
    kv_len = meta_ref[2, bi]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # rows of this q tile that are real queries, and the furthest key any of
    # them may see (the causal frontier, clipped to the cache's valid prefix)
    row_hi = jnp.minimum(q_len - qi * bq, bq)
    kv_limit = jnp.minimum(kv_len, q_off + qi * bq + row_hi)

    @pl.when((row_hi > 0) & (j * bs < kv_limit))
    def _tile():
        row = (qi * bq
               + jax.lax.broadcasted_iota(jnp.int32, (bq * g, bs), 0) // g)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bq * g, bs), 1)
        mask = (row < q_len) & (pos < kv_len) & (pos <= q_off + row)
        for h in range(nkv):                  # kv head h serves q heads h*g..
            q = q_ref[:, h * g:(h + 1) * g, :].astype(jnp.float32)  # (bq,g,hd)
            q = q.reshape(bq * g, q.shape[-1]) * scale
            k = k_ref[:, h, :].astype(jnp.float32)                  # (bs, hd)
            v = v_ref[:, h, :].astype(jnp.float32)                  # (bs, hdv)

            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)                         # (bq*g, bs)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))  # (bq*g, 1)
            # all-masked rows have m_new == NEG_INF and exp(s - m_new) == 1;
            # re-masking p keeps their l at 0 so the flush emits exact zeros
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        for h in range(nkv):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-20)
            o_ref[:, h * g:(h + 1) * g, :] = out.reshape(
                bq, g, -1).astype(o_ref.dtype)


def _kv_tile_index(bi, qi, j, m, *, bq: int, bs: int):
    """Clamp the KV tile index to the slot's frontier tile.

    Past the frontier the compute is pl.when-gated off anyway; clamping the
    index map means consecutive grid steps keep asking for the SAME block,
    which the Pallas pipeline recognizes and does not re-fetch — dead cache
    lines never leave HBM.
    """
    row_hi = jnp.minimum(m[1, bi] - qi * bq, bq)
    kv_limit = jnp.minimum(m[2, bi], m[0, bi] + qi * bq + row_hi)
    last = jnp.maximum((kv_limit - 1) // bs, 0)
    return bi, jnp.minimum(j, last), 0, 0


def _scratch(nkv: int, rows: int, hdv: int) -> list:
    """Per-kv-head online-softmax state: running max m, sum l, and acc."""
    return [pltpu.VMEM((nkv, rows, 1), jnp.float32),
            pltpu.VMEM((nkv, rows, 1), jnp.float32),
            pltpu.VMEM((nkv, rows, hdv), jnp.float32)]


@functools.partial(jax.jit,
                   static_argnames=("bq", "bs", "scale", "interpret"))
def flash_chunk(q, k, v, q_offset, q_len, kv_len, *, bq: int = 128,
                bs: int = 512, scale: float = None,
                interpret: bool = False):
    """q (B, sq, nq, hd); k (B, S, nkv, hd); v (B, S, nkv, hdv);
    q_offset / q_len / kv_len (B,) int32 -> (B, sq, nq, hdv).

    Slot ``i``'s first ``q_len[i]`` rows are real queries at absolute
    positions ``q_offset[i] + r``; rows past ``q_len[i]`` come back as
    exact zeros.  ``scale`` defaults to ``hd ** -0.5``.
    """
    b, sq, nq, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    g = nq // nkv
    if scale is None:
        scale = hd ** -0.5
    bq = min(bq, sq)
    bs = min(bs, skv)
    pq, ps = (-sq) % bq, (-skv) % bs
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if ps:
        k = jnp.pad(k, ((0, 0), (0, ps), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, ps), (0, 0), (0, 0)))
    sqp, sp = sq + pq, skv + ps

    meta = jnp.stack([
        jnp.broadcast_to(jnp.atleast_1d(q_offset), (b,)),
        jnp.broadcast_to(jnp.atleast_1d(q_len), (b,)),
        jnp.broadcast_to(jnp.atleast_1d(kv_len), (b,)),
    ]).astype(jnp.int32)                                  # (3, B)

    kv_index = functools.partial(_kv_tile_index, bq=bq, bs=bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, sqp // bq, sp // bs),
        in_specs=[
            pl.BlockSpec((None, bq, nq, hd),
                         lambda bi, qi, j, m: (bi, qi, 0, 0)),
            pl.BlockSpec((None, bs, nkv, hd), kv_index),
            pl.BlockSpec((None, bs, nkv, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, bq, nq, hdv),
                               lambda bi, qi, j, m: (bi, qi, 0, 0)),
        scratch_shapes=_scratch(nkv, bq * g, hdv),
    )
    out = pl.pallas_call(
        functools.partial(_flash_chunk_kernel, bq=bq, bs=bs, g=g, nkv=nkv,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sqp, nq, hdv), q.dtype),
        interpret=interpret,
    )(meta, q, k, v)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# Paged variant: KV tiles fetched through a per-slot block table
# ---------------------------------------------------------------------------

def _paged_kernel(meta_ref, bt_ref, *rest, **kw):
    # the block table is consumed by the index maps only; the kernel body is
    # the DENSE body verbatim (rows/positions are logical) — which is what
    # makes the paged output bit-identical to flash_chunk at equal (bq, bs)
    _flash_chunk_kernel(meta_ref, *rest, **kw)


def _paged_kv_tile_index(bi, qi, j, m, bt, *, bq: int, bs: int, ps: int):
    """Frontier-clamped logical KV tile ``j`` -> (page, tile-in-page).

    Same causal-frontier clamp as ``_kv_tile_index`` (dead tiles re-request
    the resident block), then the logical tile routes through the slot's
    block table: page ``bt[bi, tile // (ps // bs)]``.  Unallocated entries
    (−1, beyond the slot's length by the host allocator's invariant) clamp
    to page 0 — their keys sit past ``kv_len`` and are masked in-kernel.
    """
    row_hi = jnp.minimum(m[1, bi] - qi * bq, bq)
    kv_limit = jnp.minimum(m[2, bi], m[0, bi] + qi * bq + row_hi)
    last = jnp.maximum((kv_limit - 1) // bs, 0)
    jj = jnp.minimum(j, last)
    tpp = ps // bs                    # KV tiles per page
    page = jnp.maximum(bt[bi, jj // tpp], 0)
    return page, jj % tpp, 0, 0


@functools.partial(jax.jit,
                   static_argnames=("bq", "bs", "scale", "interpret"))
def flash_chunk_paged(q, k_pages, v_pages, block_tables, q_offset, q_len,
                      kv_len, *, bq: int = 128, bs: int = None,
                      scale: float = None, interpret: bool = False):
    """``flash_chunk`` against a PAGED cache: q (B, sq, nq, hd);
    k_pages (P, page, nkv, hd); v_pages (P, page, nkv, hdv);
    block_tables (B, max_blocks) int32 -> (B, sq, nq, hdv).

    Slot ``i``'s logical KV row ``t`` lives at
    ``pages[block_tables[i, t // page], t % page]``; the indirection is
    resolved entirely in the BlockSpec index map (scalar-prefetched block
    tables), so the kernel body — and therefore the arithmetic, the online
    softmax order, and the result bits — is ``flash_chunk``'s verbatim.
    ``bs`` must divide the page size (default: one tile per page).
    """
    b, sq, nq, hd = q.shape
    n_pages, page = k_pages.shape[0], k_pages.shape[1]
    nkv = k_pages.shape[2]
    hdv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    g = nq // nkv
    if scale is None:
        scale = hd ** -0.5
    bs = page if bs is None else min(bs, page)
    if page % bs:
        raise ValueError(f"bs {bs} must divide the page size {page}")
    bq = min(bq, sq)
    pq = (-sq) % bq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    sqp = sq + pq

    meta = jnp.stack([
        jnp.broadcast_to(jnp.atleast_1d(q_offset), (b,)),
        jnp.broadcast_to(jnp.atleast_1d(q_len), (b,)),
        jnp.broadcast_to(jnp.atleast_1d(kv_len), (b,)),
    ]).astype(jnp.int32)                                  # (3, B)

    kv_index = functools.partial(_paged_kv_tile_index, bq=bq, bs=bs, ps=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # meta (3, B) + block tables (B, nb)
        grid=(b, sqp // bq, nb * (page // bs)),
        in_specs=[
            pl.BlockSpec((None, bq, nq, hd),
                         lambda bi, qi, j, m, bt: (bi, qi, 0, 0)),
            pl.BlockSpec((None, bs, nkv, hd), kv_index),
            pl.BlockSpec((None, bs, nkv, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, bq, nq, hdv),
                               lambda bi, qi, j, m, bt: (bi, qi, 0, 0)),
        scratch_shapes=_scratch(nkv, bq * g, hdv),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bq=bq, bs=bs, g=g, nkv=nkv,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sqp, nq, hdv), q.dtype),
        interpret=interpret,
    )(meta, block_tables.astype(jnp.int32), q, k_pages, v_pages)
    return out[:, :sq]


__all__ = ["flash_chunk", "flash_chunk_paged"]
