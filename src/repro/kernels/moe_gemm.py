"""Grouped expert GEMMs — the MoE hot-spot — as Pallas TPU kernels.

Two variants share the same MXU-tiled matmul-pipeline structure (innermost
grid dimension walks H so the f32 VMEM accumulator lives across sequential
grid steps; HBM->VMEM streaming of x/w tiles, MXU dot per step):

  moe_gemm      capacity-mode batched GEMM on dense (E, C, H) dispatch
                buffers — one (expert, C-tile, D-tile) output block per grid
                cell.  Compute volume is E*C rows regardless of load.

  grouped_gemm  dropless-mode segment GEMM on a ragged (N, H) buffer sorted
                by expert, with an ``group_offsets`` (E+1,) prefix-sum
                delimiting each expert's rows.  The grid walks *tile visits*:
                each row tile is visited once per expert segment overlapping
                it (megablocks/Megatron "grouped GEMM" under TPU
                constraints), so compute volume is sum(counts) = N = T*k rows
                — independent of E and of the capacity factor.  Segment
                boundaries are dynamic *values* (static shapes), delivered to
                the index maps through scalar prefetch.  The weights may
                be one layer's (E, H, D) experts or the whole (L, E, H, D)
                layer stack with a scalar layer index (scalar-prefetched
                too), which a layer scan hands over without slicing it.

TPU adaptation (DESIGN.md §2): the paper's NPU/GPU expert GEMMs become one
MXU-tiled grouped GEMM over the dispatch buffers that the fused AR-A2A
communication delivers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bc", "bd", "bh", "interpret"))
def moe_gemm(x, w, *, bc: int = 128, bd: int = 128, bh: int = 128,
             interpret: bool = False):
    """x (E, C, H) @ w (E, H, D) -> (E, C, D), per-expert batched."""
    e, c, h = x.shape
    d = w.shape[-1]
    bc, bh, bd = min(bc, c), min(bh, h), min(bd, d)
    pc, ph, pd = (-c) % bc, (-h) % bh, (-d) % bd
    if pc or ph:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, ph)))
    if ph or pd:
        w = jnp.pad(w, ((0, 0), (0, ph), (0, pd)))
    cp, hp, dp = c + pc, h + ph, d + pd

    out = pl.pallas_call(
        _moe_gemm_kernel,
        grid=(e, cp // bc, dp // bd, hp // bh),
        in_specs=[
            pl.BlockSpec((1, bc, bh), lambda ei, ci, di, hi: (ei, ci, hi)),
            pl.BlockSpec((1, bh, bd), lambda ei, ci, di, hi: (ei, hi, di)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd),
                               lambda ei, ci, di, hi: (ei, ci, di)),
        out_shape=jax.ShapeDtypeStruct((e, cp, dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :c, :d]


# ---------------------------------------------------------------------------
# Dropless segment GEMM (group-offset grid)
# ---------------------------------------------------------------------------

def _group_metadata(group_offsets, n: int, bn: int, e: int,
                    num_tiles: int, n_visits: int):
    """Per-visit schedule for the segment GEMM — all static shapes.

    A *visit* is one (row-tile, expert) pair with a non-empty intersection;
    a row tile that straddles a segment boundary is visited once per
    overlapping expert, with out-of-segment rows masked in the kernel.
    Returns (5, n_visits) int32: [tile_id, group_id, row_start, row_end,
    first_visit_of_tile].

    Every tile gets exactly one first_visit=1 write: row tiles past the
    ragged extent (offsets[-1]) receive one all-masked visit each, so their
    output blocks are written with exact zeros — never left uninitialized
    (interpret mode would leave NaN; native TPU, undefined VMEM).  Visits
    beyond that land on the last tile with first_visit=0 and an empty row
    range, accumulating an exact zero into defined data.
    """
    counts = group_offsets[1:] - group_offsets[:-1]              # (E,)
    first_tile = group_offsets[:-1] // bn
    last_tile = jnp.maximum(group_offsets[1:] - 1, 0) // bn
    tiles_touched = jnp.where(counts > 0, last_tile - first_tile + 1, 0)
    cs = jnp.cumsum(tiles_touched)                               # (E,)
    total = cs[-1]
    v = jnp.arange(n_visits, dtype=jnp.int32)
    g = jnp.searchsorted(cs, v, side="right").astype(jnp.int32)
    g = jnp.minimum(g, e - 1)
    visit_start = cs[g] - tiles_touched[g]
    tile = first_tile[g] + (v - visit_start)
    valid = v < total
    # trailing tiles not covered by any segment: one zero-writing visit each
    lt = jnp.where(total > 0,
                   (jnp.maximum(group_offsets[-1], 1) - 1) // bn, -1)
    pad_tile = lt + 1 + (v - total)                   # for v in [total, ...)
    pad = (~valid) & (pad_tile <= num_tiles - 1)
    tile_ids = jnp.where(valid, tile,
                         jnp.where(pad, pad_tile,
                                   num_tiles - 1)).astype(jnp.int32)
    group_ids = jnp.where(valid, g, 0).astype(jnp.int32)
    row_start = jnp.where(valid, group_offsets[g], 0).astype(jnp.int32)
    row_end = jnp.where(valid, group_offsets[g + 1], 0).astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), tile_ids[:-1]])
    first = ((valid | pad) & (tile_ids != prev)).astype(jnp.int32)
    return jnp.stack([tile_ids, group_ids, row_start, row_end, first])


def _grouped_gemm_kernel(meta_ref, layer_ref, x_ref, w_ref, o_ref, acc_ref,
                         *, bn: int):
    del layer_ref                     # read by the weight index map only
    vi = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = (meta_ref[0, vi] * bn
            + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0))
    mask = (rows >= meta_ref[2, vi]) & (rows < meta_ref[3, vi])
    xt = jnp.where(mask, x_ref[...], jnp.zeros_like(x_ref[...]))
    acc_ref[...] += jnp.dot(xt, w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...].astype(o_ref.dtype)
        # revisited tiles (segment boundary inside the tile) accumulate into
        # the still-VMEM-resident output block; the first visit overwrites it
        o_ref[...] = jnp.where(meta_ref[4, vi] == 1, acc, o_ref[...] + acc)


def w_pad(h: int, d: int, bh: int, bd: int) -> bool:
    """Whether ``grouped_gemm`` pads its weight operand at these blocks."""
    return bool((-h) % min(bh, h) or (-d) % min(bd, d))


@functools.partial(jax.jit, static_argnames=("bn", "bd", "bh", "interpret"))
def grouped_gemm(x, w, group_offsets, layer=None, *, bn: int = 128,
                 bd: int = 128, bh: int = 128, interpret: bool = False):
    """x (N, H) sorted by expert @ w (E, H, D) -> (N, D), per-segment.

    ``group_offsets`` (E+1,) int32 ascending prefix-sum: expert e owns rows
    [offsets[e], offsets[e+1]).  Rows at/after ``offsets[-1]`` belong to no
    segment; their output is exact zeros (the ragged EP exchange pads with
    zero rows that callers never read, but the kernel still writes every
    block — no uninitialized output memory).

    ``w`` may also be a layer stack (L, E, H, D) with a scalar ``layer``:
    the kernel then reads that layer's experts where they lie, through the
    weight block's index map, so no per-layer copy of the stack is made.
    Blocks that do not divide H or D make the wrapper pad ``w`` — for a
    stack, one layer sliced out and padded.
    """
    n, h = x.shape
    if w.ndim == 3:
        w, layer = w[None], 0
    elif layer is None:
        raise ValueError("a (L, E, H, D) weight stack needs its layer index")
    n_layers, e, _, d = w.shape
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, n_layers - 1)
    bn, bh, bd = min(bn, n), min(bh, h), min(bd, d)
    pn, ph, pd = (-n) % bn, (-h) % bh, (-d) % bd
    if pn or ph:
        x = jnp.pad(x, ((0, pn), (0, ph)))
    if ph or pd:
        w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=True)
        w, layer = jnp.pad(w, ((0, 0), (0, 0), (0, ph), (0, pd))), 0
    np_, hp, dp = n + pn, h + ph, d + pd
    num_tiles = np_ // bn
    # static visit bound: every tile once + one extra visit per boundary
    n_visits = num_tiles + min(e, max(n, 1))
    meta = _group_metadata(group_offsets.astype(jnp.int32), n, bn, e,
                           num_tiles, n_visits)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(dp // bd, n_visits, hp // bh),
        in_specs=[
            pl.BlockSpec((bn, bh), lambda di, vi, hi, m, l: (m[0, vi], hi)),
            pl.BlockSpec((None, None, bh, bd),
                         lambda di, vi, hi, m, l: (l[0], m[1, vi], hi, di)),
        ],
        out_specs=pl.BlockSpec((bn, bd),
                               lambda di, vi, hi, m, l: (m[0, vi], di)),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_gemm_kernel, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, dp), x.dtype),
        interpret=interpret,
    )(meta, jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
    return out[:n, :d]


__all__ = ["moe_gemm", "grouped_gemm"]
