"""The plain reference: the configuration's forward pass in float32 at the
highest matmul precision, written from the configuration file alone.

It imports nothing of the serving program and takes nothing it made: the
weights are drawn again from the seed (``weights.Weights``), one layer and
one block of experts at a time, so that the reference fits on the chip
beside nothing else.  Sequences are packed into one row with a segment id
per token, and attention masks across segments, so one compiled program
per layer kind serves every sample.

``fp8=True`` is the control: every matrix product takes its inputs rounded
to float8 (e4m3, scaled per row of activations and per output column of
weights), the step below the bfloat16 that the configurations state.

Semantics (the program computes the same, see ``arch._REQUIRED``): pre-norm
RMSNorm with gain 1 + g, rotary embeddings on the two halves of each head,
softmax routing over all experts, top-k renormalised to sum 1, SwiGLU
experts plus shared experts, MLA with decoupled rope keys expanded per
head, untied output head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128          # query rows per attention block
T_CHUNK = 1024         # token rows per expert-block pass
E8_MAX = 448.0         # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(spec, x, w, fp8: bool, w_in_axes):
    """einsum in f32 at the highest precision; the control rounds both
    inputs to float8 first (activations per row, weights per output
    column: ``w_in_axes`` are the weight's contracted axes)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if fp8:
        x = _q8(x, axis=-1)
        w = _q8(w, axis=w_in_axes)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, pos, theta):
    """x (T, n, d) rotated by position (T,), halves convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("scale", "nkv"))
def _attend(q, k, v, seg, *, scale, nkv):
    """Causal attention within segments.  q (T, nq, dq), k (T, nkv, dq),
    v (T, nkv, dv) -> (T, nq, dv); query blocks of Q_BLOCK rows."""
    t, nq, _ = q.shape
    g = nq // nkv
    kidx = jnp.arange(t)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qs = qs.reshape(Q_BLOCK, nkv, g, -1)
        s = jnp.einsum("qhgd,khd->hgqk", qs, k, precision=HIGHEST) * scale
        qidx = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qseg = jax.lax.dynamic_slice_in_dim(seg, i * Q_BLOCK, Q_BLOCK)
        ok = (kidx[None, :] <= qidx[:, None]) & (seg[None, :] == qseg[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(ok[None, None], p, 0.0)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        return o.reshape(Q_BLOCK, nq, -1)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, nq, -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _gqa_qkv(x, w, pos, *, eps, theta, fp8):
    xn = _rms(x, w["norm"], eps)
    q = _mm("th,hnd->tnd", xn, w["wq"], fp8, (0,))
    k = _mm("th,hnd->tnd", xn, w["wk"], fp8, (0,))
    v = _mm("th,hnd->tnd", xn, w["wv"], fp8, (0,))
    return _rope(q, pos, theta), _rope(k, pos, theta), v


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8", "hd"))
def _mla_qkv(x, w, pos, *, eps, theta, fp8, hd):
    xn = _rms(x, w["norm"], eps)
    ql = _rms(_mm("th,hr->tr", xn, w["w_dq"], fp8, (0,)), w["q_norm"], eps)
    q = _mm("tr,rnd->tnd", ql, w["w_uq"], fp8, (0,))
    q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], pos, theta)], -1)
    c = _rms(_mm("th,hr->tr", xn, w["w_dkv"], fp8, (0,)), w["kv_norm"], eps)
    kr = _rope(_mm("th,hr->tr", xn, w["w_kr"], fp8, (0,))[:, None], pos,
               theta)
    kn = _mm("tr,rnd->tnd", c, w["w_uk"], fp8, (0,))
    v = _mm("tr,rnd->tnd", c, w["w_uv"], fp8, (0,))
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, kn.shape[:2]
                                              + (kr.shape[-1],))], -1)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("fp8",))
def _attn_out(x, o, wo, *, fp8):
    return x + _mm("tnd,ndh->th", o, wo, fp8, (0, 1))


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense_ffn(x, w, *, eps, fp8):
    xn = _rms(x, w["norm"], eps)
    up = _mm("th,hf->tf", xn, w["w_in"], fp8, (0,))
    gate = _mm("th,hf->tf", xn, w["w_gate"], fp8, (0,))
    return x + _mm("tf,fh->th", jax.nn.silu(gate) * up, w["w_out"], fp8,
                   (0,))


@functools.partial(jax.jit, static_argnames=("eps", "k", "fp8"))
def _route(x, norm, router, *, eps, k, fp8):
    """(normalised tokens, dense (T, E) gate weights of the top-k, each
    token's routing margin: its k-th router logit less its (k+1)-th)."""
    xn = _rms(x, norm, eps)
    logits = _mm("th,he->te", xn, router, fp8, (0,))
    probs = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], top_i].set(top_p)
    if k < logits.shape[-1]:
        top_l = jax.lax.top_k(logits, k + 1)[0]
        margin = top_l[:, k - 1] - top_l[:, k]
    else:
        margin = jnp.full(logits.shape[:1], jnp.inf)
    return xn, gates, margin


@functools.partial(jax.jit, static_argnames=("fp8",))
def _experts(xn, gates, w_in, w_gate, w_out, *, fp8):
    """sum_e gates[:, e] * SwiGLU_e(xn) over one block of experts."""
    t, h = xn.shape

    def chunk(args):
        xc, gc = args
        up = _mm("th,ehf->tef", xc, w_in, fp8, (1,))
        gate = _mm("th,ehf->tef", xc, w_gate, fp8, (1,))
        y = _mm("tef,efh->teh", jax.nn.silu(gate) * up, w_out, fp8, (1,))
        return jnp.einsum("teh,te->th", y, gc, precision=HIGHEST)

    n = t // T_CHUNK
    out = jax.lax.map(chunk, (xn.reshape(n, T_CHUNK, h),
                              gates.reshape(n, T_CHUNK, -1)))
    return out.reshape(t, h)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _shared(xn, w_in, w_gate, w_out, *, fp8):
    up = _mm("th,hf->tf", xn, w_in, fp8, (0,))
    gate = _mm("th,hf->tf", xn, w_gate, fp8, (0,))
    return _mm("tf,fh->th", jax.nn.silu(gate) * up, w_out, fp8, (0,))


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, *, eps, fp8):
    return _mm("th,hv->tv", _rms(x, norm, eps), head, fp8, (0,))


def pack(seqs) -> tuple:
    """Sequences -> (tokens, segment ids, positions), padded to a bucket
    of T_CHUNK rows (padding is segment -1, seen by no real row)."""
    n = sum(len(s) for s in seqs)
    t = T_CHUNK * 2 ** max(0, math.ceil(math.log2(max(n, 1) / T_CHUNK)))
    toks = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    o = 0
    for i, s in enumerate(seqs):
        toks[o:o + len(s)] = s
        seg[o:o + len(s)] = i
        pos[o:o + len(s)] = np.arange(len(s))
        o += len(s)
    return toks, seg, pos


class Reference:
    """Forward passes of one configuration under one seed's weights."""

    def __init__(self, arch, seed: int, *, fp8: bool = False):
        self.a, self.fp8 = arch, fp8
        self.w = Weights(arch, seed)
        # expert blocks of at most ~0.8 GB of bfloat16 weights
        per = 3 * arch.d_model * max(arch.d_expert, 1) * 2
        eb = max(1, min(arch.n_experts, int(8e8 // per)))
        while arch.n_experts % eb:
            eb -= 1
        self.eb = eb

    def _layer_w(self, path, li, names):
        return {n: self.w.block(f"{path}/{n}", (li,)) for n in names}

    def logits(self, seqs, rows):
        """Logits (len(rows), V) in float32 at packed rows ``rows`` of the
        packed ``seqs`` (see ``pack``)."""
        a, fp8 = self.a, self.fp8
        toks, seg, pos = (jnp.asarray(v) for v in pack(seqs))
        self.margin = jnp.full(toks.shape, jnp.inf)
        embed = self.w.block("embed", ())
        x = jnp.take(embed, toks, axis=0).astype(F32)
        del embed
        kinds = ["dense"] * a.first_dense \
            + ["moe"] * (a.n_layers - a.first_dense)
        for layer, kind in enumerate(kinds):
            g = 0 if (kind == "dense" or not a.first_dense) else 1
            li = layer if g == 0 else layer - a.first_dense
            x = self._attention(x, seg, pos, f"groups/{g}/attn", li)
            if kind == "dense":
                w = self._layer_w(f"groups/{g}/mlp", li,
                                  ("norm", "w_in", "w_gate", "w_out"))
                x = _dense_ffn(x, w, eps=a.eps, fp8=fp8)
            else:
                x = self._moe(x, f"groups/{g}/moe", li)
        rows = jnp.asarray(np.asarray(rows, np.int32))
        self.margin = jnp.take(self.margin, rows)
        return _head(jnp.take(x, rows, axis=0), self.w.block("final_norm", ()),
                     self.w.block("lm_head", ()), eps=a.eps, fp8=fp8)

    def _attention(self, x, seg, pos, path, li):
        a, fp8 = self.a, self.fp8
        if a.attention == "mla":
            w = self._layer_w(path, li, ("norm", "w_dq", "q_norm", "w_uq",
                                         "w_dkv", "kv_norm", "w_kr", "w_uk",
                                         "w_uv"))
            q, k, v = _mla_qkv(x, w, pos, eps=a.eps, theta=a.rope_theta,
                               fp8=fp8, hd=a.head_dim)
            nkv, scale = a.n_heads, (a.head_dim + a.rope_dim) ** -0.5
        else:
            w = self._layer_w(path, li, ("norm", "wq", "wk", "wv"))
            q, k, v = _gqa_qkv(x, w, pos, eps=a.eps, theta=a.rope_theta,
                               fp8=fp8)
            nkv, scale = a.n_kv_heads, a.head_dim ** -0.5
        del w
        o = _attend(q, k, v, seg, scale=float(scale), nkv=nkv)
        del q, k, v
        return _attn_out(x, o, self.w.block(f"{path}/wo", (li,)), fp8=fp8)

    def _moe(self, x, path, li):
        a, fp8 = self.a, self.fp8
        xn, gates, margin = _route(x, self.w.block(f"{path}/norm", (li,)),
                                   self.w.block(f"{path}/router", (li,)),
                                   eps=a.eps, k=a.top_k, fp8=fp8)
        self.margin = jnp.minimum(self.margin, margin)
        for e0 in range(0, a.n_experts, self.eb):
            ws = [self.w.block(f"{path}/{n}", (li, e0), self.eb)
                  for n in ("w_in", "w_gate", "w_out")]
            x = x + _experts(xn, gates[:, e0:e0 + self.eb], *ws, fp8=fp8)
            del ws
        if a.n_shared:
            ws = [self.w.block(f"{path}/{n}", (li,))
                  for n in ("shared_in", "shared_gate", "shared_out")]
            x = x + _shared(xn, *ws, fp8=fp8)
        return x


def served_rows(samples):
    """For (prompt, served tokens) pairs: the packed sequences (prompt plus
    every served token but the last), the packed row that predicts each
    served token, and those tokens."""
    seqs, rows, toks = [], [], []
    o = 0
    for prompt, out in samples:
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        seqs.append(seq)
        rows.extend(o + len(prompt) - 1 + np.arange(len(out)))
        toks.extend(out.tolist())
        o += len(seq)
    return seqs, np.asarray(rows, np.int32), np.asarray(toks, np.int32)


@jax.jit
def _gaps(logits, toks):
    best = jnp.max(logits, -1)
    return best - jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]


def served_gaps(arch, seed: int, samples) -> tuple:
    """How far below the reference's best logit each served token's logit
    lies, for (prompt, served tokens) pairs (0 where the token is the
    reference's own greedy choice), and the routing margin of the row
    that predicted it: the smallest, over the MoE layers, of its k-th
    router logit less its (k+1)-th."""
    seqs, rows, toks = served_rows(samples)
    with jax.default_matmul_precision("highest"):
        ref = Reference(arch, seed)
        lg = ref.logits(seqs, rows)
        return np.asarray(_gaps(lg, jnp.asarray(toks))), \
            np.asarray(ref.margin)


def control_gaps(arch, seed: int, samples) -> tuple:
    """The control: at the same positions, the gap (under the float32
    reference) of the token that the float8 reference puts first, with
    the float32 reference's routing margins as ``served_gaps`` gives
    them."""
    seqs, rows, _ = served_rows(samples)
    with jax.default_matmul_precision("highest"):
        lo = Reference(arch, seed, fp8=True).logits(seqs, rows)
        first = jnp.argmax(lo, -1).astype(jnp.int32)
        del lo
        ref = Reference(arch, seed)
        lg = ref.logits(seqs, rows)
        return np.asarray(_gaps(lg, first)), np.asarray(ref.margin)
