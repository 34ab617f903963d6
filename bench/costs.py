"""Operations and bytes that each kernel's call and each served token need,
computed from shapes.  A roofline share is the least time these need at the
chip's peaks, over the measured time; a step's model-FLOP share counts only
the work of real tokens.
"""

from __future__ import annotations

BF16 = 2


def roofline_s(flops: float, nbytes: float, peaks) -> float:
    """Least time for the work: the larger of its compute and memory terms."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)


def grouped_gemm(rows: int, k: int, n: int, groups: int) -> tuple:
    """x (rows, k) sorted into ``groups`` segments @ w (groups, k, n).  With
    rows >= groups every group's weights are read once."""
    flops = 2.0 * rows * k * n
    nbytes = BF16 * (rows * k + min(groups, rows) * k * n + rows * n)
    return flops, nbytes


def attention(q_len: int, kv_off: int, n_q: int, d_qk: int, d_v: int,
              n_kv: int) -> tuple:
    """Causal attention of ``q_len`` new rows over ``kv_off`` cached rows
    plus themselves: QK^T and PV over the visible pairs; the cache and the
    new rows' K/V read once, queries read and outputs written once."""
    if q_len <= 0:
        return 0.0, 0.0
    pairs = q_len * kv_off + q_len * (q_len + 1) / 2
    flops = 2.0 * n_q * (d_qk + d_v) * pairs
    kv = (kv_off + q_len) * n_kv * (d_qk + d_v)
    nbytes = BF16 * (kv + q_len * n_q * (d_qk + d_v))
    return flops, nbytes


def token_flops(a, kv_len: int) -> float:
    """Model FLOPs of one token through every layer at context ``kv_len``:
    every matrix it meets (the top-k routed experts and the shared ones
    only) plus attention over its context.  The output head is counted per
    sampled row (``head_flops``)."""
    d, nh = a.d_model, a.n_heads
    if a.attention == "mla":
        proj = (d * a.q_rank + a.q_rank * nh * (a.head_dim + a.rope_dim)
                + d * (a.kv_rank + a.rope_dim)
                + a.kv_rank * nh * (a.head_dim + a.v_head_dim)
                + nh * a.v_head_dim * d)
        att = nh * (a.head_dim + a.rope_dim + a.v_head_dim) * kv_len
    else:
        proj = d * (nh + 2 * a.n_kv_heads) * a.head_dim \
            + nh * a.head_dim * d
        att = nh * 2 * a.head_dim * kv_len
    dense_ffn = 3 * d * a.d_ff
    moe_ffn = 3 * d * a.d_expert * (a.top_k + a.n_shared) + d * a.n_experts
    n_moe = a.n_layers - a.first_dense
    per = a.n_layers * (proj + att) + a.first_dense * dense_ffn \
        + n_moe * moe_ffn
    return 2.0 * per


def head_flops(a) -> float:
    return 2.0 * a.d_model * a.vocab
