"""Fused token permute / unpermute+combine for MoE dispatch (Pallas TPU).

The jnp dispatch path materializes a zero (E, C, h) buffer, ``jnp.repeat``s
every token k times and scatter-adds the copies in HBM; the combine path
gathers (T*k, h) rows and reduces.  Both round-trip the full activation set
through HBM twice.  These kernels collapse each direction into a single
gather pass driven by precomputed int32 index vectors (Megatron-Core's
"fused token permutation/unpermutation" under TPU constraints):

  permute_tokens         out[i] = x[src_tok[i]]        (src_tok < 0 -> 0 row)
  permute_tokens_ragged  same, plus a dynamic row count so tiles past the
                         ragged extent skip the gather loop.  Monolithic
                         dropless EP exchange buffers are worst-case sized;
                         under the micro-chunked overlap schedule
                         (models.moe, plan.ep_overlap) each chunk's buffer
                         is COUNT-BOUNDED to ``ep * cap_rows_for(...)``
                         rows, so the static extent this kernel walks is
                         already near the ragged fill — the tile skipping
                         then only trims the cap's sigma headroom
  unpermute_tokens       out[t] = sum_j buf[src_slot[t,j]] * w[t,j]
                         (already segment-agnostic: it reads ragged buffers
                         through the same index vectors)

The index vectors are tiny (ints, not h-wide rows): the inverse map costs
one int32 scatter over E*C elements instead of a (T*k, h) float scatter-add.

TPU form.  The index vectors arrive through scalar prefetch (SMEM), and
every row moves by its own DMA out of an HBM (``pl.ANY``) source, so the
source never has to fit in VMEM and no kernel loads a dynamic, unaligned
row from a vector register file.  A DMA may only cut whole tiles, so rows
travel as ``(rows, 1, words)`` views of 32-bit words: one bf16 pair or one
f32 per word (``_as_words``), whose ``(1, words)`` trailing dims are one
tile row.  ``unpermute_tokens`` unpacks the words to f32 in VMEM, combines
there, and packs the rounded result back.

``models.moe.scatter_to_buffers`` / ``gather_from_buffers`` build the index
vectors from their DispatchInfo and call these via ``kernels.ops`` when the
KernelPolicy enables ``fused_permute``; ``kernels.ref`` holds the jnp
oracles the interpret-mode sweeps assert against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

_HI = np.uint32(0xFFFF0000)      # the upper bf16 of a packed word


def _as_words(x):
    """(n, h) 16- or 32-bit rows -> (n, 1, words) uint32, words packed
    pairwise for 16-bit types (h must then be even)."""
    n, h = x.shape
    if x.dtype.itemsize == 2:
        if h % 2:
            raise ValueError(f"16-bit rows need an even width, got {h}")
        x = x.reshape(n, h // 2, 2)
    elif x.dtype.itemsize != 4:
        raise ValueError(f"unsupported row dtype {x.dtype}")
    return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(n, 1, -1)


def _from_words(w, dtype):
    """Inverse of ``_as_words``: (n, [1,] words) uint32 -> (n, h) dtype."""
    n = w.shape[0]
    return jax.lax.bitcast_convert_type(w.reshape(n, -1), dtype).reshape(n, -1)


def _gather_rows(idx_ref, base: int, x_hbm, o_hbm, zero_ref, sem, bn: int):
    """o_hbm[base + i] = x_hbm[idx[base + i]] for i < bn, one DMA per row;
    a negative index copies the zero row instead."""
    def copy(i, src):
        return pltpu.make_async_copy(src, o_hbm.at[base + i], sem)

    def start(i, _):
        tok = idx_ref[base + i]

        @pl.when(tok >= 0)
        def _row():
            copy(i, x_hbm.at[tok]).start()

        @pl.when(tok < 0)
        def _zero():
            copy(i, zero_ref.at[0]).start()
        return 0

    def wait(i, _):
        copy(i, zero_ref.at[0]).wait()      # every row moved the same bytes
        return 0

    jax.lax.fori_loop(0, bn, start, 0)
    jax.lax.fori_loop(0, bn, wait, 0)


def _permute_kernel(idx_ref, x_hbm, o_hbm, zero_ref, sem, *, bn: int):
    zero_ref[...] = jnp.zeros_like(zero_ref)
    _gather_rows(idx_ref, pl.program_id(0) * bn, x_hbm, o_hbm, zero_ref,
                 sem, bn)


def _permute_call(kernel, n_prefetch: int, n_rows: int, bn: int, words: int,
                  interpret: bool):
    """pallas_call over ``n_rows // bn`` row tiles: HBM source in, HBM
    output written by DMA only, a zero-row scratch and one DMA semaphore."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(n_rows // bn,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((bn, 1, words), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, 1, words), jnp.uint32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def permute_tokens(x, src_tok, *, bn: int = None, interpret: bool = False):
    """x (T, h), src_tok (N,) int32 -> (N, h); src_tok[i] < 0 yields a 0 row.

    One gather pass: row i of the output is token ``src_tok[i]``.  With
    ``src_tok`` the inverse of the (expert, position) assignment this IS the
    dispatch scatter, without the zeros+repeat+scatter-add HBM traffic.
    """
    n, h = src_tok.shape[0], x.shape[1]
    if bn is None:
        bn = autotune.select_blocks("permute", (n, h), x.dtype)["bn"]
    bn = min(bn, n)
    pn = (-n) % bn
    if pn:
        src_tok = jnp.pad(src_tok, (0, pn), constant_values=-1)
    xw = _as_words(x)
    out = _permute_call(functools.partial(_permute_kernel, bn=bn), 1,
                        n + pn, bn, xw.shape[-1], interpret,
                        )(src_tok.astype(jnp.int32), xw)
    return _from_words(out[:n], x.dtype)


def _permute_ragged_kernel(cnt_ref, idx_ref, x_hbm, o_hbm, zero_ref, sem, *,
                           bn: int, seg_stride: int, n_seg: int):
    base = pl.program_id(0) * bn
    zero_ref[...] = jnp.zeros_like(zero_ref)
    # a tile fully inside one segment whose local offset is past that
    # segment's populated prefix holds no data; straddling tiles always
    # gather (conservative — their -1 entries yield zero rows anyway)
    r0 = jnp.minimum(base // seg_stride, n_seg - 1)
    r1 = jnp.minimum((base + bn - 1) // seg_stride, n_seg - 1)
    empty = (r0 == r1) & ((base - r0 * seg_stride) >= cnt_ref[r0])

    @pl.when(empty)
    def _skip():                      # one DMA zeroes the whole tile
        cp = pltpu.make_async_copy(zero_ref, o_hbm.at[pl.ds(base, bn)], sem)
        cp.start()
        cp.wait()

    @pl.when(~empty)
    def _gather():
        _gather_rows(idx_ref, base, x_hbm, o_hbm, zero_ref, sem, bn)


@functools.partial(jax.jit, static_argnames=("seg_stride", "bn", "interpret"))
def permute_tokens_ragged(x, src_tok, total, *, seg_stride: int = None,
                          bn: int = None, interpret: bool = False):
    """Segment-aware ``permute_tokens``: the output is a sequence of
    fixed-stride segments each populated only in a leading prefix, and
    tiles that lie entirely in a segment's empty tail skip the per-row
    gather and are zeroed by a single DMA.

    ``total`` is either a () scalar — one segment spanning the whole
    buffer with ``total`` valid leading rows — or a (n_seg,) int32 vector
    of per-segment prefix counts with segments at ``seg_stride`` row
    intervals (the dropless EP send layout: destination rank r's rows
    live at [r*seg_stride, r*seg_stride + counts[r])).  Rows outside the
    prefixes must carry ``src_tok == -1`` (they come back as zero rows
    either way).  The buffers are sized for the worst-case skew, so at
    low load most tiles are empty."""
    n, h = src_tok.shape[0], x.shape[1]
    totals = jnp.asarray(total, jnp.int32).reshape(-1)    # () -> (1,)
    n_seg = totals.shape[0]
    if seg_stride is None:
        seg_stride = n
    if n_seg * seg_stride < n:
        raise ValueError(f"segments ({n_seg} x {seg_stride}) do not cover "
                         f"the {n}-row buffer")
    if bn is None:
        bn = autotune.select_blocks("permute", (n, h), x.dtype)["bn"]
    bn = min(bn, n)
    pn = (-n) % bn
    if pn:
        src_tok = jnp.pad(src_tok, (0, pn), constant_values=-1)
    xw = _as_words(x)
    out = _permute_call(
        functools.partial(_permute_ragged_kernel, bn=bn,
                          seg_stride=seg_stride, n_seg=n_seg),
        2, n + pn, bn, xw.shape[-1], interpret,
    )(totals, src_tok.astype(jnp.int32), xw)
    return _from_words(out[:n], x.dtype)


def _unpack(w, dtype):
    """(rows, words) uint32 -> the f32 planes of its elements: one plane
    for 32-bit types, [even, odd] element planes for bf16."""
    if dtype == jnp.float32:
        return [jax.lax.bitcast_convert_type(w, jnp.float32)]
    lo = jax.lax.bitcast_convert_type(w << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(w & _HI, jnp.float32)
    return [lo, hi]


def _pack(planes, dtype):
    """Inverse of ``_unpack`` after rounding each plane to ``dtype``."""
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(planes[0], jnp.uint32)
    lo, hi = (jax.lax.bitcast_convert_type(
        p.astype(dtype).astype(jnp.float32), jnp.uint32) for p in planes)
    return (hi & _HI) | (lo >> 16)


def _unpermute_kernel(slot_ref, w_ref, buf_hbm, o_ref, rows_ref, sem, *,
                      bn: int, k: int, dtype):
    base = pl.program_id(0) * bn

    def copy(i, j, s):
        return pltpu.make_async_copy(buf_hbm.at[s], rows_ref.at[j, i], sem)

    def walk(i, _, *, wait: bool):
        for j in range(k):             # k is 2..8 — unrolled slot walk
            s = slot_ref[(base + i) * k + j]

            @pl.when(s >= 0)
            def _row():
                if wait:
                    copy(i, j, 0).wait()
                else:
                    copy(i, j, s).start()
        return 0

    jax.lax.fori_loop(0, bn, functools.partial(walk, wait=False), 0)
    jax.lax.fori_loop(0, bn, functools.partial(walk, wait=True), 0)

    def combine(i, _):
        # one token row at a time, so each row's arithmetic is the same for
        # any tile height; rows of dropped slots were never fetched —
        # select them away, don't multiply them
        zero = jnp.zeros(rows_ref.shape[2:], jnp.float32)        # (1, words)
        acc = [zero] * (1 if dtype == jnp.float32 else 2)
        for j in range(k):
            f = (base + i) * k + j
            ok, wj = slot_ref[f] >= 0, w_ref[f]
            acc = [a + jnp.where(ok, r * wj, 0.0)
                   for a, r in zip(acc, _unpack(rows_ref[j, i], dtype))]
        o_ref[i] = _pack(acc, dtype)
        return 0

    jax.lax.fori_loop(0, bn, combine, 0)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def unpermute_tokens(buf, src_slot, weights, *, bn: int = None,
                     interpret: bool = False):
    """buf (M, h), src_slot (T, k) int32, weights (T, k) -> (T, h).

    Weighted combine fused with the inverse permutation: token t's output is
    the f32-accumulated sum of its k expert rows, each scaled by its routing
    weight (dropped slots carry src_slot < 0 and contribute 0).  ``buf`` is
    float32 or bfloat16.
    """
    h = buf.shape[1]
    t, k = src_slot.shape
    if buf.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(f"unpermute_tokens needs f32 or bf16, got {buf.dtype}")
    if bn is None:
        bn = autotune.select_blocks("unpermute", (t, h), buf.dtype)["bn"]
    bn = min(bn, t)
    pt = (-t) % bn
    if pt:
        src_slot = jnp.pad(src_slot, ((0, pt), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, pt), (0, 0)))
    tp = t + pt
    bw = _as_words(buf)
    words = bw.shape[-1]

    out = pl.pallas_call(
        functools.partial(_unpermute_kernel, bn=bn, k=k, dtype=buf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,        # slots and weights, flattened
            grid=(tp // bn,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bn, 1, words), lambda i, s, w: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, bn, 1, words), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, 1, words), jnp.uint32),
        interpret=interpret,
    )(src_slot.astype(jnp.int32).reshape(-1),
      weights.astype(jnp.float32).reshape(-1), bw)
    return _from_words(out[:t], buf.dtype)


__all__ = ["permute_tokens", "permute_tokens_ragged", "unpermute_tokens"]
