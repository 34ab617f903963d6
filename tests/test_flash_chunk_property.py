"""Property sweep of the ragged mixed-chunk flash-attention kernel.

Random per-slot ``q_len``/``q_offset``/``kv_len`` mixes — all-idle,
single-slot, full-chunk, ragged-tail — against the ``chunked_attention``
jnp oracle (the masked chunked-softmax body the kernel replaces on the
unified serving hot path), for GQA and MLA-absorbed head shapes.

``hypothesis`` is not in the base container image; CI installs it (the
module skips cleanly without it).  Runs in the fast ``-m kernels`` lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.models.layers import chunked_attention  # noqa: E402

pytestmark = pytest.mark.kernels

KEY = jax.random.PRNGKey(0)

# (nq, nkv, hd, hdv): GQA, and MLA-absorbed (single latent kv head, latent
# keys wider than the bare-latent values)
HEAD_SHAPES = {"gqa": (8, 2, 32, 32), "mla": (4, 1, 40, 24)}


@st.composite
def ragged_batches(draw):
    b = draw(st.integers(1, 4))
    sq = draw(st.integers(1, 8))
    q_lens = draw(st.lists(st.integers(0, sq), min_size=b, max_size=b))
    margin = draw(st.integers(0, 24))        # cache slack past the frontier
    offsets = [draw(st.integers(0, 24)) if ql else 0 for ql in q_lens]
    skv = max(o + ql for o, ql in zip(offsets, q_lens)) + margin
    skv = max(skv, sq, 1)
    return b, sq, q_lens, offsets, skv


@pytest.mark.parametrize("head", sorted(HEAD_SHAPES))
@settings(max_examples=25, deadline=None)
@example(batch=(1, 4, [0], [0], 8))                       # all-idle
@example(batch=(1, 6, [6], [5], 16))                      # single full slot
@example(batch=(3, 4, [4, 1, 0], [0, 9, 0], 16))          # mixed step
@example(batch=(2, 8, [3, 8], [13, 0], 24))               # ragged tails
@given(batch=ragged_batches())
def test_flash_chunk_matches_oracle(head, batch):
    b, sq, q_lens, offsets, skv = batch
    nq, nkv, hd, hdv = HEAD_SHAPES[head]
    q = jax.random.normal(KEY, (b, sq, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, skv, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, skv, nkv, hdv))
    off = jnp.asarray(offsets, jnp.int32)
    qlen = jnp.asarray(q_lens, jnp.int32)
    kvlen = off + qlen

    got = ops.flash_chunk(q, k, v, off, qlen, kvlen, bq=4, bs=16)
    assert bool(jnp.isfinite(got).all())
    want = chunked_attention(q, k, v, q_offset=off, kv_len=kvlen,
                             causal=True)
    for i in range(b):                 # oracle tail rows are garbage
        ql = int(qlen[i])
        np.testing.assert_allclose(np.asarray(got[i, :ql]),
                                   np.asarray(want[i, :ql]),
                                   atol=2e-5, rtol=2e-5)
        # kernel tail rows are exact zeros
        assert float(jnp.max(jnp.abs(got[i, ql:]), initial=0.0)) == 0.0


@pytest.mark.parametrize("head", sorted(HEAD_SHAPES))
@settings(max_examples=25, deadline=None)
@example(batch=(1, 4, [0], [0], 8), seed=0)                # all-idle
@example(batch=(1, 6, [6], [5], 16), seed=0)               # single full slot
@example(batch=(3, 4, [4, 1, 0], [0, 9, 0], 16), seed=0)   # mixed step
@example(batch=(2, 8, [3, 8], [13, 0], 24), seed=0)        # ragged tails
@given(batch=ragged_batches(), seed=st.integers(0, 2 ** 16))
def test_flash_chunk_paged_bit_identical_to_dense(head, batch, seed):
    """Page indirection through a PERMUTED block table never changes the
    bits: flash_chunk_paged == flash_chunk at the same bq/bs, for any
    ragged slot mix (the shared-prefix serving invariant)."""
    b, sq, q_lens, offsets, skv = batch
    nq, nkv, hd, hdv = HEAD_SHAPES[head]
    page = 8
    nb = -(-max(skv, 1) // page)
    s = nb * page                       # pad the cache to whole pages
    q = jax.random.normal(KEY, (b, sq, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hdv))
    off = jnp.asarray(offsets, jnp.int32)
    qlen = jnp.asarray(q_lens, jnp.int32)
    kvlen = off + qlen

    # scatter each slot's logical blocks to permuted pool pages
    rng = np.random.default_rng(seed)
    perm = rng.permutation(b * nb).reshape(b, nb).astype(np.int32)
    kp = jnp.zeros((b * nb, page, nkv, hd), jnp.float32)
    vp = jnp.zeros((b * nb, page, nkv, hdv), jnp.float32)
    for i in range(b):
        for j in range(nb):
            kp = kp.at[perm[i, j]].set(k[i, j * page:(j + 1) * page])
            vp = vp.at[perm[i, j]].set(v[i, j * page:(j + 1) * page])
    bt = jnp.asarray(perm)

    dense = ops.flash_chunk(q, k, v, off, qlen, kvlen, bq=4, bs=page)
    paged = ops.flash_chunk_paged(q, kp, vp, bt, off, qlen, kvlen,
                                  bq=4, bs=page)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))
