"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs the jnp oracle.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
on a TPU backend the same wrappers lower natively.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.kernels.moe_gemm import w_pad

pytestmark = pytest.mark.kernels

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,h,d", [
    (2, 64, 64, 64), (4, 128, 128, 128), (1, 100, 96, 200),
    (8, 48, 256, 128), (3, 130, 70, 90),
])
def test_moe_gemm_sweep(e, c, h, d, dtype):
    x = jax.random.normal(KEY, (e, c, h), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (e, h, d), jnp.float32)
         / np.sqrt(h)).astype(dtype)
    got = ops.moe_gemm(x, w)
    want = ops.moe_gemm_ref(x, w)
    assert got.shape == (e, c, d) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("t,e,k", [
    (64, 8, 2), (256, 16, 2), (100, 160, 6), (17, 4, 3), (512, 64, 8),
])
def test_topk_gate_sweep(t, e, k):
    logits = jax.random.normal(KEY, (t, e), jnp.float32)
    gw, gi = ops.topk_gate(logits, k)
    ww, wi = ops.topk_gate_ref(logits, k)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nq,nkv,hd,s", [
    (2, 8, 2, 64, 256), (1, 4, 1, 32, 77), (4, 16, 16, 64, 512),
    (2, 28, 4, 128, 300),
])
def test_flash_decode_sweep(b, nq, nkv, hd, s, dtype):
    q = jax.random.normal(KEY, (b, nq, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd), dtype)
    lens = jnp.asarray(
        np.random.default_rng(0).integers(1, s + 1, b), jnp.int32)
    got = ops.flash_decode(q, k, v, lens, bs=128)
    want = ops.flash_decode_ref(q, k, v, lens)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_decode_matches_model_decode_attention():
    """Kernel == the model's pure-JAX decode path (same masking semantics)."""
    from repro.models.layers import decode_attention
    b, nq, nkv, hd, s = 2, 8, 4, 32, 128
    q = jax.random.normal(KEY, (b, 1, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    lens = jnp.asarray([50, 128], jnp.int32)
    jnp_out = decode_attention(q, k, v, kv_len=lens,
                               q_positions=(lens - 1)[:, None])
    krn_out = ops.flash_decode(q[:, 0], k, v, lens)
    np.testing.assert_allclose(np.asarray(jnp_out[:, 0]),
                               np.asarray(krn_out), atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,nq,nkv,hd,hdv,s", [
    (3, 8, 8, 2, 64, 64, 256),      # GQA, mixed chunk
    (2, 16, 4, 1, 40, 24, 96),      # MLA-absorbed-like: nkv=1, hdv != hd
    (4, 1, 15, 5, 16, 16, 77),      # decode shape (sq == 1, ragged heads)
    (2, 5, 6, 6, 32, 32, 33),       # MHA, nothing tile-aligned
])
def test_flash_chunk_sweep(b, sq, nq, nkv, hd, hdv, s, dtype):
    """Ragged mixed-chunk kernel == the jnp oracle across slot mixes:
    idle (q_len 0), decode (1), short chunk, full chunk, at random cache
    offsets — GQA and MLA-absorbed (hdv != hd) head shapes."""
    rng = np.random.default_rng(b * sq + s)
    q = jax.random.normal(KEY, (b, sq, nq, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hdv), dtype)
    qlen = rng.integers(0, sq + 1, b).astype(np.int32)
    qlen[0] = 0                                     # always one idle slot
    off = np.asarray([rng.integers(0, s - ql + 1) for ql in qlen], np.int32)
    kvlen = off + qlen
    got = ops.flash_chunk(q, k, v, jnp.asarray(off), jnp.asarray(qlen),
                          jnp.asarray(kvlen), bq=4, bs=32)
    want = ops.flash_chunk_ref(q, k, v, jnp.asarray(off), jnp.asarray(qlen),
                               jnp.asarray(kvlen))
    assert got.shape == (b, sq, nq, hdv) and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # ragged tails / idle slots are exact zeros, never NaN
    tail = np.asarray(got, np.float32)[np.arange(sq)[None] >= qlen[:, None]]
    assert np.all(tail == 0.0)


def test_flash_chunk_all_idle_zero_work():
    """An all-idle batch (every q_len == 0) writes finite exact-zero output
    — the case the old flash_decode needed a caller-side length floor for."""
    b, sq, nq, nkv, hd, s = 2, 4, 8, 2, 32, 64
    q = jax.random.normal(KEY, (b, sq, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    zero = jnp.zeros((b,), jnp.int32)
    got = ops.flash_chunk(q, k, v, zero, zero, zero, bq=4, bs=32)
    assert float(jnp.max(jnp.abs(got))) == 0.0
    assert not bool(jnp.isnan(got).any())


def test_flash_decode_is_flash_chunk_sq1_specialization():
    """flash_decode == flash_chunk at sq == 1 with the decode invariant
    (q_offset = len-1, q_len = 1, kv_len = len), incl. a len == 0 slot."""
    b, nq, nkv, hd, s = 3, 8, 4, 32, 128
    q = jax.random.normal(KEY, (b, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    lens = jnp.asarray([0, 50, 128], jnp.int32)
    dec = ops.flash_decode(q, k, v, lens, bs=64)
    chk = ops.flash_chunk(q[:, None], k, v, jnp.maximum(lens - 1, 0),
                          jnp.minimum(lens, 1), lens, bq=1, bs=64)[:, 0]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(chk))
    # live slots match the decode oracle; the len==0 slot is exact zeros
    want = ops.flash_decode_ref(q[1:], k[1:], v[1:], lens[1:])
    np.testing.assert_allclose(np.asarray(dec[1:]), np.asarray(want),
                               atol=1e-4)
    assert float(jnp.max(jnp.abs(dec[0]))) == 0.0


def test_flash_chunk_matches_chunked_attention_oracle():
    """Kernel == the model's masked chunked-softmax body over valid rows
    (the unified step's vector q_offset/kv_len semantics)."""
    from repro.models.layers import chunked_attention
    b, sq, nq, nkv, hd, s = 3, 8, 8, 2, 32, 64
    q = jax.random.normal(KEY, (b, sq, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    off = jnp.asarray([0, 13, 40], jnp.int32)
    qlen = jnp.asarray([8, 3, 0], jnp.int32)
    jnp_out = chunked_attention(q, k, v, q_offset=off, kv_len=off + qlen,
                                causal=True)
    krn_out = ops.flash_chunk(q, k, v, off, qlen, off + qlen, bq=4, bs=32)
    for i, ql in enumerate([8, 3, 0]):       # jnp tail rows are garbage
        np.testing.assert_allclose(np.asarray(krn_out[i, :ql]),
                                   np.asarray(jnp_out[i, :ql]), atol=2e-5)


def _paged_case(rng, b, sq, nkv, hd, hdv, page, nb, pool, dtype,
                *, scatter=True):
    """Random paged-attention instance: dense k/v plus an equivalent page
    pool reached through a (scattered) block table."""
    q = jax.random.normal(KEY, (b, sq, nkv * 2, hd), dtype)
    s = nb * page
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hdv), dtype)
    qlen = rng.integers(0, sq + 1, b).astype(np.int32)
    qlen[0] = 0                                     # always one idle slot
    off = np.asarray([rng.integers(0, s - ql + 1) for ql in qlen], np.int32)
    kvlen = off + qlen
    # scatter each slot's nb logical blocks to distinct pool pages
    perm = (rng.permutation(pool) if scatter
            else np.arange(pool))[:b * nb].reshape(b, nb).astype(np.int32)
    kp = jnp.zeros((pool, page, nkv, hd), dtype)
    vp = jnp.zeros((pool, page, nkv, hdv), dtype)
    for i in range(b):
        for j in range(nb):
            kp = kp.at[perm[i, j]].set(k[i, j * page:(j + 1) * page])
            vp = vp.at[perm[i, j]].set(v[i, j * page:(j + 1) * page])
    # unallocated blocks past the frontier are -1 in the real table
    bt = np.where(np.arange(nb)[None] * page < np.maximum(kvlen, 1)[:, None],
                  perm, -1).astype(np.int32)
    return (q, k, v, kp, vp, jnp.asarray(bt), jnp.asarray(off),
            jnp.asarray(qlen), jnp.asarray(kvlen))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,nkv,hd,hdv,page,nb,pool", [
    (3, 8, 2, 64, 64, 32, 4, 16),    # GQA, scattered pages + slack pool
    (2, 16, 1, 40, 24, 16, 4, 8),    # MLA-absorbed-like: nkv=1, hdv != hd
    (4, 1, 4, 32, 32, 16, 3, 12),    # decode shape (sq == 1)
    (2, 5, 3, 32, 32, 16, 2, 4),     # exactly-full pool, ragged heads
])
def test_flash_chunk_paged_sweep(b, sq, nkv, hd, hdv, page, nb, pool, dtype):
    """Paged kernel == paged jnp oracle across slot mixes and SCATTERED
    (permuted) block tables, GQA and MLA-absorbed head shapes."""
    rng = np.random.default_rng(b * sq + page)
    (q, _k, _v, kp, vp, bt, off, qlen, kvlen) = _paged_case(
        rng, b, sq, nkv, hd, hdv, page, nb, pool, dtype)
    got = ops.flash_chunk_paged(q, kp, vp, bt, off, qlen, kvlen,
                                bq=4, bs=page)
    want = ops.flash_chunk_paged_ref(q, kp, vp, bt, off, qlen, kvlen)
    assert got.shape == (b, sq, nkv * 2, hdv) and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    tail = np.asarray(got, np.float32)[
        np.arange(sq)[None] >= np.asarray(qlen)[:, None]]
    assert np.all(tail == 0.0)


@pytest.mark.parametrize("scatter", [False, True])
def test_flash_chunk_paged_bit_identical_to_dense(scatter):
    """THE paging correctness bar: page indirection (contiguous AND
    permuted tables) changes where tiles are read, never the arithmetic —
    outputs are bitwise equal to dense flash_chunk at the same bq/bs."""
    b, sq, nkv, hd, page, nb, pool = 3, 8, 2, 32, 32, 3, 12
    rng = np.random.default_rng(7 if scatter else 8)
    (q, k, v, kp, vp, bt, off, qlen, kvlen) = _paged_case(
        rng, b, sq, nkv, hd, hd, page, nb, pool, jnp.float32,
        scatter=scatter)
    dense = ops.flash_chunk(q, k, v, off, qlen, kvlen, bq=4, bs=page)
    paged = ops.flash_chunk_paged(q, kp, vp, bt, off, qlen, kvlen,
                                  bq=4, bs=page)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))
    # sub-page KV tiles route through the same table: still bitwise equal
    half = ops.flash_chunk_paged(q, kp, vp, bt, off, qlen, kvlen,
                                 bq=4, bs=page // 2)
    dense_half = ops.flash_chunk(q, k, v, off, qlen, kvlen,
                                 bq=4, bs=page // 2)
    np.testing.assert_array_equal(np.asarray(dense_half), np.asarray(half))


def test_flash_chunk_paged_shared_prefix_page():
    """Two slots whose block tables alias the SAME pool page (a shared
    prompt prefix) both read it correctly — no copy, same bits."""
    b, sq, nkv, hd, page, nb, pool = 2, 4, 2, 32, 16, 2, 4
    q = jax.random.normal(KEY, (b, sq, nkv * 2, hd), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (pool, page, nkv, hd))
    vp = jax.random.normal(jax.random.PRNGKey(2), (pool, page, nkv, hd))
    bt = jnp.asarray([[3, 1], [3, 2]], jnp.int32)   # page 3 shared
    off = jnp.asarray([page + 2, page + 5], jnp.int32)
    qlen = jnp.asarray([sq, sq], jnp.int32)
    kvlen = off + qlen
    got = ops.flash_chunk_paged(q, kp, vp, bt, off, qlen, kvlen,
                                bq=4, bs=page)
    want = ops.flash_chunk_paged_ref(q, kp, vp, bt, off, qlen, kvlen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # each slot == its own dense gather (prefix aliasing is transparent)
    for i in range(b):
        ki = kp[bt[i]].reshape(1, nb * page, nkv, hd)
        vi = vp[bt[i]].reshape(1, nb * page, nkv, hd)
        solo = ops.flash_chunk(q[i:i + 1], ki, vi, off[i:i + 1],
                               qlen[i:i + 1], kvlen[i:i + 1],
                               bq=4, bs=page)
        np.testing.assert_array_equal(np.asarray(solo[0]),
                                      np.asarray(got[i]))


def test_flash_chunk_paged_rejects_non_dividing_bs():
    q = jnp.zeros((1, 4, 2, 16), jnp.float32)
    kp = jnp.zeros((2, 16, 1, 16), jnp.float32)
    vp = jnp.zeros((2, 16, 1, 16), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    z = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="divide"):
        ops.flash_chunk_paged(q, kp, vp, bt, z, z, z, bq=4, bs=12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,h,n,frac", [
    (16, 32, 40, 0.5), (100, 64, 256, 0.9), (7, 48, 7, 1.0),
    (256, 128, 300, 0.0),
])
def test_permute_tokens_sweep(t, h, n, frac, dtype):
    """Fused permute == gather oracle across fill fractions (frac = share of
    output rows that carry a token; the rest are -1 -> zero rows)."""
    x = jax.random.normal(KEY, (t, h), dtype)
    rng = np.random.default_rng(0)
    src = np.full((n,), -1, np.int32)
    fill = rng.choice(n, size=int(n * frac), replace=False)
    src[fill] = rng.integers(0, t, size=fill.size)
    got = ops.permute_tokens(x, jnp.asarray(src))
    want = ops.permute_tokens_ref(x, jnp.asarray(src))
    assert got.shape == (n, h) and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,k,h,m", [
    (16, 2, 32, 64), (100, 6, 64, 320), (7, 3, 48, 21), (256, 8, 128, 512),
])
def test_unpermute_tokens_sweep(t, k, h, m, dtype):
    buf = jax.random.normal(KEY, (m, h), dtype)
    slot = jax.random.randint(jax.random.PRNGKey(1), (t, k), -1, m)
    w = jax.random.uniform(jax.random.PRNGKey(2), (t, k), jnp.float32)
    got = ops.unpermute_tokens(buf, slot, w)
    want = ops.unpermute_tokens_ref(buf, slot, w)
    assert got.shape == (t, h) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,d,e,skew", [
    (64, 64, 64, 4, "uniform"), (100, 96, 200, 8, "uniform"),
    (128, 64, 96, 16, "one_expert"),      # full skew: every row one expert
    (96, 48, 64, 6, "empty_experts"),     # half the experts get nothing
    (33, 40, 56, 5, "uniform"),           # ragged, nothing tile-aligned
])
def test_grouped_gemm_sweep(n, h, d, e, skew, dtype):
    """Dropless segment GEMM == ragged_dot oracle across skews, including
    empty experts and segment boundaries inside row tiles."""
    rng = np.random.default_rng(0)
    if skew == "one_expert":
        counts = np.zeros(e, np.int64)
        counts[e // 2] = n
    elif skew == "empty_experts":
        counts = rng.multinomial(n, [2 / e if i % 2 else 0.0
                                     for i in range(e)])
    else:
        counts = rng.multinomial(n, np.ones(e) / e)
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    x = jax.random.normal(KEY, (n, h), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (e, h, d), jnp.float32)
         / np.sqrt(h)).astype(dtype)
    got = ops.grouped_gemm(x, w, offs, bn=16, bd=32, bh=32)
    want = ops.grouped_gemm_ref(x, w, offs)
    assert got.shape == (n, d) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_grouped_gemm_partial_buffer_zero_tail():
    """Rows at/after offsets[-1] (the unused tail of a worst-case-sized EP
    exchange buffer) come back as exact zeros — never uninitialized."""
    n, h, d, e, m_real = 96, 32, 48, 4, 21
    rng = np.random.default_rng(1)
    counts = rng.multinomial(m_real, np.ones(e) / e)
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    x = np.zeros((n, h), np.float32)
    x[:m_real] = rng.standard_normal((m_real, h))
    w = jnp.asarray(rng.standard_normal((e, h, d)) / np.sqrt(h), jnp.float32)
    got = ops.grouped_gemm(jnp.asarray(x), w, offs, bn=8, bd=16, bh=16)
    want = ops.grouped_gemm_ref(jnp.asarray(x), w, offs)
    np.testing.assert_allclose(np.asarray(got[:m_real]),
                               np.asarray(want[:m_real]), atol=1e-5)
    assert float(jnp.max(jnp.abs(got[m_real:]))) == 0.0
    assert not bool(jnp.isnan(got).any())


def _segments(n, e, skew, rng):
    if skew == "one_expert":
        counts = np.zeros(e, np.int64)
        counts[e // 2] = n
    elif skew == "empty_experts":
        counts = rng.multinomial(n, [2 / e if i % 2 else 0.0
                                     for i in range(e)])
    else:
        counts = rng.multinomial(n, np.ones(e) / e)
    return jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)


@pytest.mark.parametrize("n,m_real,h,d,e,skew", [
    (64, 64, 128, 640, 4, "uniform"),     # 640: 128 divides it, 512 not
    (96, 70, 256, 640, 5, "empty_experts"),   # zero tail past offsets[-1]
    (48, 48, 128, 1408, 3, "one_expert"),     # 1408 = 11 x 128
    (80, 57, 1408, 128, 6, "uniform"),        # 1408 along H, zero tail
])
def test_grouped_gemm_stacked_layer(n, m_real, h, d, e, skew):
    """The (L, E, H, D) layer stack with a layer index == the oracle on
    w[layer], with the default tiles, which divide every width here: the
    weights are read in place, never padded."""
    n_layers = 3
    rng = np.random.default_rng(n + d)
    offs = _segments(m_real, e, skew, rng)
    x = np.zeros((n, h), np.float32)
    x[:m_real] = rng.standard_normal((m_real, h))
    x = jnp.asarray(x, jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (n_layers, e, h, d),
                           jnp.float32) / np.sqrt(h)).astype(jnp.bfloat16)
    ops.reset_counters()
    for layer in range(n_layers):
        got = ops.grouped_gemm(x, w, offs, jnp.int32(layer))
        want = ops.grouped_gemm_ref(x, w[layer], offs)
        assert got.shape == (n, d) and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got[:m_real], np.float32),
                                   np.asarray(want[:m_real], np.float32),
                                   atol=2e-2, rtol=2e-2)
        assert not np.asarray(got[m_real:], np.float32).any()
    assert ops.counters["grouped_gemm_stacked"] == n_layers
    assert ops.counters["grouped_gemm_w_pad"] == 0


def test_grouped_gemm_stacked_pad_fallback():
    """A width that no multiple of 128 divides: the wrapper pads one layer
    sliced out of the stack, and still matches the oracle."""
    n, h, d, e, n_layers = 40, 96, 200, 3, 2
    offs = _segments(n, e, "uniform", np.random.default_rng(2))
    x = jax.random.normal(KEY, (n, h), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (n_layers, e, h, d),
                          jnp.float32) / np.sqrt(h)
    ops.reset_counters()
    got = ops.grouped_gemm(x, w, offs, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ops.grouped_gemm_ref(x, w[1], offs)),
                               atol=1e-5, rtol=1e-5)
    assert ops.counters["grouped_gemm_w_pad"] == 1
    assert ops.counters["grouped_gemm_stacked"] == 0


@pytest.mark.parametrize("shape", [
    (1024, 4096, 6400, 16), (1024, 6400, 4096, 16),     # phi3.5-moe
    (3072, 2048, 1408, 64), (3072, 1408, 2048, 64),     # DeepSeek-V2-Lite
    (1024, 5120, 1536, 160),                            # DeepSeek-V2
    (512, 4096, 200, 16), (64, 1000, 1000, 4),          # no 128 divides
])
def test_grouped_gemm_default_blocks_divide_widths(shape):
    n, h, d, _e = shape
    b = autotune._default_blocks("grouped_gemm", shape, "bfloat16")
    bn, bh, bd = b["bn"], b["bh"], b["bd"]
    assert n % bn == 0
    assert (autotune.grouped_gemm_vmem(bn, bh, bd, 2)
            <= autotune.SCOPED_VMEM_BYTES * 7 // 8)
    # x, w and f32 accumulator tiles within the per-step working-set budget
    assert (bn * bh * 2 + bh * bd * 2 + bn * bd * 4
            <= autotune.VMEM_BUDGET_BYTES)
    divisible = h % 128 == 0 and d % 128 == 0
    assert w_pad(h, d, bh, bd) == (not divisible)
    if divisible:                # a wide contraction and a full MXU pass
        assert bh >= 512 and bd >= 512
    for dim, blk in ((h, bh), (d, bd)):
        assert blk % 128 == 0 and blk <= dim - dim % 128
        if dim % 128 == 0:
            assert dim % blk == 0


@pytest.mark.parametrize("t,h,n,fill", [
    (16, 32, 64, 0), (32, 64, 256, 100), (20, 48, 48, 48), (8, 16, 128, 3),
])
def test_permute_tokens_ragged_sweep(t, h, n, fill):
    """Segment-aware ragged permute == gather oracle: rows past the dynamic
    ``total`` are -1 (zero rows) and whole empty tiles are skipped."""
    rng = np.random.default_rng(0)
    x = jax.random.normal(KEY, (t, h), jnp.float32)
    src = np.full((n,), -1, np.int32)
    src[:fill] = rng.integers(0, t, size=fill)
    got = ops.permute_tokens_ragged(x, jnp.asarray(src), fill, bn=8)
    want = ops.permute_tokens_ref(x, jnp.asarray(src))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_seg,stride,bn", [
    (4, 32, 8), (8, 16, 8), (3, 40, 16), (2, 64, 128),
])
def test_permute_tokens_ragged_per_segment(n_seg, stride, bn):
    """The EP send layout: per-destination-rank prefixes at fixed stride,
    NOT one contiguous prefix.  Valid rows of every segment — including
    the last ranks' — must survive tile elision."""
    rng = np.random.default_rng(1)
    t, h = 24, 32
    x = jax.random.normal(KEY, (t, h), jnp.float32)
    n = n_seg * stride
    counts = rng.integers(0, stride + 1, n_seg).astype(np.int32)
    src = np.full((n,), -1, np.int32)
    for s in range(n_seg):
        src[s * stride:s * stride + counts[s]] = rng.integers(
            0, t, size=counts[s])
    got = ops.permute_tokens_ragged(x, jnp.asarray(src),
                                    jnp.asarray(counts),
                                    seg_stride=stride, bn=bn)
    want = ops.permute_tokens_ref(x, jnp.asarray(src))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_permute_matches_moe_dispatch():
    """Kernel round trip == the jnp scatter/gather dispatch path, including
    capacity-dropped slots (cf tight enough to drop with random routing)."""
    from repro.models import moe as M
    t, h, e, k = 64, 32, 8, 2
    x = jax.random.normal(KEY, (t, h), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (t, k), 0, e)
    w = jax.random.uniform(jax.random.PRNGKey(2), (t, k), jnp.float32)
    cap = M.capacity_for(t, k, e, 1.0)
    d = M.make_dispatch(idx, w, e, cap)
    assert not bool(d.keep.all()), "want dropped slots in this scenario"

    buf_jnp = M.scatter_to_buffers(x, d, e)
    buf_krn = M.scatter_to_buffers(x, d, e, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(buf_krn), np.asarray(buf_jnp))

    out_jnp = M.gather_from_buffers(buf_jnp, d, t)
    out_krn = M.gather_from_buffers(buf_jnp, d, t, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out_krn), np.asarray(out_jnp),
                               atol=1e-5)


def test_autotune_selection_cached_and_divisible():
    autotune.clear_cache()
    blocks = autotune.select_blocks("moe_gemm", (4, 300, 512, 640),
                                    jnp.float32)
    assert set(blocks) == {"bc", "bd", "bh"}
    # VMEM working set respects the budget
    assert (blocks["bc"] * blocks["bh"] * 4 + blocks["bh"] * blocks["bd"] * 4
            + blocks["bc"] * blocks["bd"] * 4) <= autotune.VMEM_BUDGET_BYTES
    # cached: same key -> identical selection, one entry
    again = autotune.select_blocks("moe_gemm", (4, 300, 512, 640),
                                   jnp.float32)
    assert again == blocks and len(autotune.cache_info()) == 1
    # registered overrides (a measured tune result) win over the default
    autotune.register("moe_gemm", (4, 300, 512, 640), jnp.float32,
                      {"bc": 64, "bd": 64, "bh": 64})
    assert autotune.select_blocks("moe_gemm", (4, 300, 512, 640),
                                  jnp.float32) == {"bc": 64, "bd": 64,
                                                   "bh": 64}
    autotune.clear_cache()


def test_autotune_tune_measures_and_registers():
    """tune() without an explicit shape must register under the SAME key the
    ops wrapper builds, so the measured override is actually reachable."""
    autotune.clear_cache()
    x = jax.random.normal(KEY, (2, 64, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    cands = [{"bc": 32, "bd": 32, "bh": 32}, {"bc": 64, "bd": 64, "bh": 64}]
    best = autotune.tune("moe_gemm", ops.moe_gemm, cands, x, w)
    assert best in cands
    assert autotune.select_blocks("moe_gemm", (2, 64, 64, 64),
                                  x.dtype) == best
    autotune.clear_cache()


def test_autotune_tune_reports_and_raises_on_failed_candidates():
    """A candidate the compiler refuses is reported, not swallowed; the
    survivors still compete, and no survivor at all is an error."""
    autotune.clear_cache()

    def fn(x, *, bn):
        if bn == 7:
            raise ValueError("block refused")
        return x * bn

    x = jnp.ones((4,), jnp.float32)
    with pytest.warns(UserWarning, match="block refused"):
        best = autotune.tune("permute", fn, [{"bn": 7}, {"bn": 8}], x,
                             shape=(4, 4), dtype=jnp.float32)
    assert best == {"bn": 8}
    with pytest.warns(UserWarning), pytest.raises(RuntimeError,
                                                  match="none of 1"):
        autotune.tune("permute", fn, [{"bn": 7}], x, shape=(4, 4),
                      dtype=jnp.float32)
    autotune.clear_cache()


def test_autotune_flash_decode_bs_tracks_kv_len():
    """The flash tile grows with the cache length S (k.shape[1]), not with
    the head dim."""
    autotune.clear_cache()
    short = autotune.select_blocks("flash_decode", (4, 256, 8, 64),
                                   jnp.float32)
    long = autotune.select_blocks("flash_decode", (4, 4096, 8, 64),
                                  jnp.float32)
    assert short["bs"] == 256 and long["bs"] == 2048
    autotune.clear_cache()


def test_autotune_flash_chunk_blocks():
    """The flash_chunk default: bq covers the (small) chunk, bs tracks the
    cache length like flash_decode's tile."""
    autotune.clear_cache()
    small = autotune.select_blocks("flash_chunk", (4, 8, 16, 64, 256),
                                   jnp.float32)
    assert small == {"bq": 8, "bs": 256}
    long = autotune.select_blocks("flash_chunk", (4, 256, 16, 64, 4096),
                                  jnp.float32)
    assert long["bq"] == 128 and long["bs"] == 2048
    autotune.clear_cache()


def test_autotune_flash_chunk_paged_bs_divides_page():
    """The paged flash default: the KV tile is the largest power-of-two
    divisor of the page (the block table routes whole tiles), the q tile
    tracks the chunk like flash_chunk's."""
    autotune.clear_cache()
    got = autotune.select_blocks("flash_chunk_paged", (4, 8, 16, 64, 28, 16),
                                 jnp.float32)
    assert got == {"bq": 8, "bs": 16}
    # big pages cap the KV tile at a power-of-two divisor
    big = autotune.select_blocks("flash_chunk_paged",
                                 (4, 128, 16, 64, 64, 4096), jnp.float32)
    assert big["bs"] <= 2048 and 4096 % big["bs"] == 0
    # odd page sizes degrade to one tile per page
    odd = autotune.select_blocks("flash_chunk_paged", (2, 8, 8, 64, 8, 24),
                                 jnp.float32)
    assert odd["bs"] in (8, 24) and 24 % odd["bs"] == 0
    autotune.clear_cache()


def test_autotune_flash_chunk_persistent_roundtrip(tmp_path, monkeypatch):
    """A persisted flash_chunk registration survives reload under the
    CURRENT cache version, and a stale-version file is invalidated
    wholesale (the version was bumped for this op's key)."""
    import json

    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_cache()
    shape = (2, 16, 8, 32, 512)
    autotune.register("flash_chunk", shape, jnp.float32,
                      {"bq": 16, "bs": 64})
    assert json.loads(path.read_text())["version"] == autotune.CACHE_VERSION
    autotune.clear_cache()              # "new process"
    assert autotune.select_blocks("flash_chunk", shape,
                                  jnp.float32) == {"bq": 16, "bs": 64}
    # rewrite the same entry under the PREVIOUS schema version: ignored
    payload = json.loads(path.read_text())
    payload["version"] = autotune.CACHE_VERSION - 1
    path.write_text(json.dumps(payload))
    autotune.clear_cache()
    got = autotune.select_blocks("flash_chunk", shape, jnp.float32)
    assert got != {"bq": 16, "bs": 64}      # analytic default instead
    autotune.clear_cache()


def test_autotune_persistent_roundtrip(tmp_path, monkeypatch):
    """register() writes through to the JSON cache; a fresh process (simulated
    by clearing the in-memory cache) lazily adopts it in select_blocks."""
    import json

    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_cache()
    # a measured override no analytic default would produce
    autotune.register("moe_gemm", (4, 300, 512, 640), jnp.float32,
                      {"bc": 32, "bd": 16, "bh": 8})
    payload = json.loads(path.read_text())
    assert payload["version"] == autotune.CACHE_VERSION
    assert len(payload["entries"]) == 1

    autotune.clear_cache()              # "new process": in-memory gone
    assert autotune.cache_info() == {}
    got = autotune.select_blocks("moe_gemm", (4, 300, 512, 640), jnp.float32)
    assert got == {"bc": 32, "bd": 16, "bh": 8}    # adopted from disk
    # a key NOT on disk still falls through to the analytic default
    other = autotune.select_blocks("moe_gemm", (2, 64, 64, 64), jnp.float32)
    assert other != got
    autotune.clear_cache(persistent=True)
    assert not path.exists()


def test_autotune_persistent_stale_version_ignored(tmp_path, monkeypatch):
    """A version-skewed cache file is invalidated wholesale: stale block
    schemas must not leak into selection."""
    import json

    path = tmp_path / "autotune.json"
    key = autotune.cache_key("moe_gemm", (4, 300, 512, 640), jnp.float32)
    path.write_text(json.dumps({
        "version": autotune.CACHE_VERSION + 999,
        "entries": {f"moe_gemm|4,300,512,640|{key.dtype}":
                    {"bc": 8, "bd": 8, "bh": 8}},
    }))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_cache()
    got = autotune.select_blocks("moe_gemm", (4, 300, 512, 640), jnp.float32)
    assert got != {"bc": 8, "bd": 8, "bh": 8}      # analytic default instead
    # a corrupt file is equally ignored (best-effort persistence)
    path.write_text("{not json")
    autotune.clear_cache()
    assert autotune.select_blocks("moe_gemm", (4, 300, 512, 640),
                                  jnp.float32) == got
    autotune.clear_cache()


def test_autotune_persistence_disabled_by_empty_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    autotune.clear_cache()
    assert autotune.cache_path() is None
    autotune.register("moe_gemm", (2, 64, 64, 64), jnp.float32,
                      {"bc": 32, "bd": 32, "bh": 32})   # no crash, no file
    assert list(tmp_path.iterdir()) == []
    autotune.clear_cache()


def test_moe_gemm_grad_matches_ref():
    """The pallas_call is differentiable in interpret mode? No — but the ops
    wrapper is only used in inference paths; verify the forward at bf16
    accumulates in f32 (no catastrophic error vs f32 oracle)."""
    e, c, h, d = 2, 64, 128, 64
    x32 = jax.random.normal(KEY, (e, c, h), jnp.float32)
    w32 = jax.random.normal(jax.random.PRNGKey(1), (e, h, d)) / np.sqrt(h)
    ref32 = ops.moe_gemm_ref(x32, w32)
    got16 = ops.moe_gemm(x32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16))
    err = float(jnp.max(jnp.abs(got16.astype(jnp.float32) - ref32)))
    assert err < 0.15   # bf16 inputs, f32 accumulation
