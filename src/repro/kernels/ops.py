"""Jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels lower natively; elsewhere (this CPU container)
they execute in interpret mode, which runs the kernel body in Python and is
what the allclose sweep tests validate against ``ref.py``.

Block sizes default to the autotune layer's shape-keyed selection
(``kernels.autotune``); callers can still pin them explicitly.

``counters`` tallies wrapper invocations at trace time — inside ``jax.jit``
each entry counts traced call sites (once per compilation), which is exactly
what the integration tests assert: the jitted serving graph *contains* the
kernel, not merely could reach it.
"""

from __future__ import annotations

import collections

import jax

from repro.kernels import autotune, ref
from repro.kernels.flash_chunk import flash_chunk as _flash_chunk
from repro.kernels.flash_chunk import flash_chunk_paged as _flash_chunk_paged
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.moe_gemm import grouped_gemm as _grouped_gemm
from repro.kernels.moe_gemm import moe_gemm as _moe_gemm
from repro.kernels.moe_gemm import w_pad
from repro.kernels.permute import permute_tokens as _permute_tokens
from repro.kernels.permute import (permute_tokens_ragged
                                   as _permute_tokens_ragged)
from repro.kernels.permute import unpermute_tokens as _unpermute_tokens
from repro.kernels.topk_gate import topk_gate as _topk_gate

counters: collections.Counter = collections.Counter()


def reset_counters() -> None:
    counters.clear()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def moe_gemm(x, w, **kw):
    counters["moe_gemm"] += 1
    kw.setdefault("interpret", _interpret())
    if not {"bc", "bd", "bh"} & kw.keys():
        e, c, h = x.shape
        blocks = autotune.select_blocks(
            "moe_gemm", (e, c, h, w.shape[-1]), x.dtype)
        kw.update(blocks)
    return _moe_gemm(x, w, **kw)


def grouped_gemm(x, w, group_offsets, layer=None, **kw):
    """``w`` is (E, H, D), or the (L, E, H, D) layer stack with ``layer``.

    Besides the call site, counts the sites that read the stack in place
    (``grouped_gemm_stacked``) and those whose wrapper pads ``w``
    (``grouped_gemm_w_pad``)."""
    counters["grouped_gemm"] += 1
    kw.setdefault("interpret", _interpret())
    n, h = x.shape
    if not {"bn", "bd", "bh"} & kw.keys():
        kw.update(autotune.select_blocks(
            "grouped_gemm", (n, h, w.shape[-1], w.shape[-3]), x.dtype))
    padded = w_pad(h, w.shape[-1], kw.get("bh", 128), kw.get("bd", 128))
    counters["grouped_gemm_w_pad"] += padded
    counters["grouped_gemm_stacked"] += w.ndim == 4 and not padded
    return _grouped_gemm(x, w, group_offsets, layer, **kw)


def topk_gate(logits, k: int, **kw):
    counters["topk_gate"] += 1
    kw.setdefault("interpret", _interpret())
    if "bt" not in kw:
        kw.update(autotune.select_blocks("topk_gate", logits.shape,
                                         logits.dtype))
    return _topk_gate(logits, k, **kw)


def flash_decode(q, k, v, lengths, **kw):
    counters["flash_decode"] += 1
    kw.setdefault("interpret", _interpret())
    if "bs" not in kw:
        kw.update(autotune.select_blocks("flash_decode", k.shape, k.dtype))
    return _flash_decode(q, k, v, lengths, **kw)


def flash_chunk(q, k, v, q_offset, q_len, kv_len, **kw):
    counters["flash_chunk"] += 1
    kw.setdefault("interpret", _interpret())
    if not {"bq", "bs"} & kw.keys():
        kw.update(autotune.select_blocks(
            "flash_chunk", tuple(q.shape) + (k.shape[1],), q.dtype))
    return _flash_chunk(q, k, v, q_offset, q_len, kv_len, **kw)


def flash_chunk_paged(q, k_pages, v_pages, block_tables, q_offset, q_len,
                      kv_len, **kw):
    counters["flash_chunk_paged"] += 1
    kw.setdefault("interpret", _interpret())
    if not {"bq", "bs"} & kw.keys():
        kw.update(autotune.select_blocks(
            "flash_chunk_paged",
            tuple(q.shape) + (k_pages.shape[0], k_pages.shape[1]), q.dtype))
    return _flash_chunk_paged(q, k_pages, v_pages, block_tables,
                              q_offset, q_len, kv_len, **kw)


def permute_tokens(x, src_tok, **kw):
    counters["permute_tokens"] += 1
    kw.setdefault("interpret", _interpret())
    if "bn" not in kw:
        kw.update(autotune.select_blocks(
            "permute", (src_tok.shape[0], x.shape[-1]), x.dtype))
    return _permute_tokens(x, src_tok, **kw)


def permute_tokens_ragged(x, src_tok, total, **kw):
    counters["permute_tokens_ragged"] += 1
    kw.setdefault("interpret", _interpret())
    if "bn" not in kw:
        kw.update(autotune.select_blocks(
            "permute", (src_tok.shape[0], x.shape[-1]), x.dtype))
    return _permute_tokens_ragged(x, src_tok, total, **kw)


def unpermute_tokens(buf, src_slot, weights, **kw):
    counters["unpermute_tokens"] += 1
    kw.setdefault("interpret", _interpret())
    if "bn" not in kw:
        kw.update(autotune.select_blocks(
            "unpermute", (src_slot.shape[0], buf.shape[-1]), buf.dtype))
    return _unpermute_tokens(buf, src_slot, weights, **kw)


# oracles re-exported for benches/tests
moe_gemm_ref = ref.moe_gemm_ref
grouped_gemm_ref = ref.grouped_gemm_ref
topk_gate_ref = ref.topk_gate_ref
flash_decode_ref = ref.flash_decode_ref
flash_chunk_ref = ref.flash_chunk_ref
flash_chunk_paged_ref = ref.flash_chunk_paged_ref
permute_tokens_ref = ref.permute_tokens_ref
permute_tokens_ragged_ref = ref.permute_tokens_ragged_ref
unpermute_tokens_ref = ref.unpermute_tokens_ref

__all__ = ["moe_gemm", "grouped_gemm", "topk_gate", "flash_decode",
           "flash_chunk", "flash_chunk_paged", "permute_tokens",
           "permute_tokens_ragged", "unpermute_tokens", "moe_gemm_ref",
           "grouped_gemm_ref", "topk_gate_ref", "flash_decode_ref",
           "flash_chunk_ref", "flash_chunk_paged_ref", "permute_tokens_ref",
           "permute_tokens_ragged_ref", "unpermute_tokens_ref", "counters",
           "reset_counters"]
