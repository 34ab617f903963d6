"""Random weights drawn from the seed, the same on every side that asks.

Each element of each weight is a pure function of (seed, the weight's
path, its flat index): a counter-based integer hash turned into a uniform
value of the weight's standard deviation and rounded once to bfloat16.  So
the serving program's whole tree is drawn on the device in one jitted call,
and the reference draws any slice of it again (one layer, a block of
experts) without ever holding the program's copy.

Standard deviations are 1/sqrt(fan_in) with the fan-in taken over the axes
that each matrix contracts (all of ``d_model`` for a query projection, heads
x head size for the output projection), the embedding has unit deviation and
every norm gain is zero (the program's norms scale by 1 + gain).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

M32 = 0xFFFFFFFF


def _leaf_table(a) -> dict:
    """path -> (shape, std); std 0 marks a zero-initialised norm gain."""
    d, nh, hd, vd, rd = a.d_model, a.n_heads, a.head_dim, a.v_head_dim, \
        a.rope_dim
    t = {"embed": ((a.vocab, d), 1.0), "final_norm": ((d,), 0.0),
         "lm_head": ((d, a.vocab), d ** -0.5)}

    def attn(g, n):
        p = f"groups/{g}/attn/"
        if a.attention == "mla":
            qr, r = a.q_rank, a.kv_rank
            t.update({
                p + "w_dq": ((n, d, qr), d ** -0.5),
                p + "q_norm": ((n, qr), 0.0),
                p + "w_uq": ((n, qr, nh, hd + rd), qr ** -0.5),
                p + "w_dkv": ((n, d, r), d ** -0.5),
                p + "kv_norm": ((n, r), 0.0),
                p + "w_kr": ((n, d, rd), d ** -0.5),
                p + "w_uk": ((n, r, nh, hd), r ** -0.5),
                p + "w_uv": ((n, r, nh, vd), r ** -0.5),
                p + "wo": ((n, nh, vd, d), (nh * vd) ** -0.5),
                p + "norm": ((n, d), 0.0)})
        else:
            nkv = a.n_kv_heads
            t.update({
                p + "wq": ((n, d, nh, hd), d ** -0.5),
                p + "wk": ((n, d, nkv, hd), d ** -0.5),
                p + "wv": ((n, d, nkv, hd), d ** -0.5),
                p + "wo": ((n, nh, hd, d), (nh * hd) ** -0.5),
                p + "norm": ((n, d), 0.0)})

    g = 0
    if a.first_dense:
        n = a.first_dense
        attn(g, n)
        p = f"groups/{g}/mlp/"
        t.update({p + "w_in": ((n, d, a.d_ff), d ** -0.5),
                  p + "w_gate": ((n, d, a.d_ff), d ** -0.5),
                  p + "w_out": ((n, a.d_ff, d), a.d_ff ** -0.5),
                  p + "norm": ((n, d), 0.0)})
        g += 1
    n = a.n_layers - a.first_dense
    if n:
        attn(g, n)
        p = f"groups/{g}/moe/"
        e, de = a.n_experts, a.d_expert
        t.update({p + "norm": ((n, d), 0.0),
                  p + "router": ((n, d, e), d ** -0.5),
                  p + "w_in": ((n, e, d, de), d ** -0.5),
                  p + "w_gate": ((n, e, d, de), d ** -0.5),
                  p + "w_out": ((n, e, de, d), de ** -0.5)})
        if a.n_shared:
            ds = de * a.n_shared
            t.update({p + "shared_in": ((n, d, ds), d ** -0.5),
                      p + "shared_gate": ((n, d, ds), d ** -0.5),
                      p + "shared_out": ((n, ds, d), ds ** -0.5)})
    for path, (shape, _) in t.items():
        if math.prod(shape) > M32:
            raise ValueError(f"{path}: {shape} has more elements than the "
                             "32-bit element counter covers")
    return t


def _mix(h: int) -> int:
    h &= M32
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & M32
    return h ^ (h >> 16)


def leaf_key(seed: int, path: str) -> np.ndarray:
    """Two 32-bit words naming (seed, path); any integer seed."""
    s = int(seed)
    lo, hi = s & M32, (s >> 32) & M32
    c = zlib.crc32(path.encode())
    k0 = _mix(lo ^ _mix(c ^ 0x9E3779B9))
    k1 = _mix(hi ^ _mix(k0 + 0x7F4A7C15))
    return np.array([k0, k1], np.uint32)


def _fmix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def draw(key, start, shape: tuple, std: float):
    """Elements [start, start + prod(shape)) of a weight, as bfloat16.

    ``key`` (2,) uint32 and ``start`` are traced, so one compiled program
    serves every seed.  (u - 1.5) * w with u exactly in [1, 2) is one
    rounding of an exact value, so every program that computes it agrees
    to the bit.
    """
    if std == 0.0:
        return jnp.zeros(shape, jnp.bfloat16)
    idx = jnp.asarray(start, jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, shape, 0) * jnp.uint32(math.prod(shape[1:]))
    for ax in range(1, len(shape)):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) \
            * jnp.uint32(math.prod(shape[ax + 1:]))
    h = _fmix(_fmix(idx ^ key[0]) + key[1])
    u = jax.lax.bitcast_convert_type((h >> 9) | jnp.uint32(0x3F800000),
                                     jnp.float32)
    width = jnp.float32(2.0 * math.sqrt(3.0) * std)
    return ((u - jnp.float32(1.5)) * width).astype(jnp.bfloat16)


class Weights:
    """The weights of one configuration under one seed."""

    def __init__(self, arch, seed: int):
        self.arch, self.seed = arch, int(seed)
        self.table = _leaf_table(arch)

    def key(self, path: str) -> np.ndarray:
        return leaf_key(self.seed, path)

    def tree_for(self, abstract):
        """Draw, in one jitted call, a pytree shaped like ``abstract`` (the
        program's parameter tree of ShapeDtypeStructs).  Every leaf's path
        and shape must be one of this configuration's weights."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
        specs = []
        for kp, leaf in flat:
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in kp)
            if path not in self.table:
                raise KeyError(f"the program holds a weight {path!r} that "
                               "the benchmark's configuration lacks")
            shape, std = self.table[path]
            if tuple(leaf.shape) != shape:
                raise ValueError(f"{path}: program shape {leaf.shape}, "
                                 f"configuration shape {shape}")
            specs.append((path, shape, std))
        keys = np.stack([self.key(p) for p, _, _ in specs])

        @jax.jit
        def make(keys):
            return [draw(keys[i], 0, shape, std)
                    for i, (_, shape, std) in enumerate(specs)]

        return jax.tree_util.tree_unflatten(treedef, make(keys))

    def block(self, path: str, lead: tuple, count: int = 1):
        """Slice of a weight: leading indices ``lead`` with the last of
        them widened to ``count`` entries (e.g. (layer, first_expert))."""
        shape, std = self.table[path]
        nl = len(lead)
        inner = shape[nl:]
        sub = (count,) + tuple(inner) if count > 1 else tuple(inner)
        strides = [math.prod(shape[i + 1:]) for i in range(len(shape))]
        start = sum(int(i) * strides[j] for j, i in enumerate(lead))
        return _draw_jit(jnp.asarray(self.key(path)), np.uint32(start),
                         shape=sub, std=std)


_draw_jit = jax.jit(draw, static_argnames=("shape", "std"))
