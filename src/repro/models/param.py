"""Minimal parameter-declaration system (no flax dependency).

A model declares its parameters as a nested dict of ``P`` objects; ``init_tree``
materializes arrays and ``axes_tree`` yields the matching pytree of *logical
axis names* that the partitioner maps to mesh axes.  Keeping shapes, init and
logical axes in one declaration is what lets the weight loader/partitioner
(paper §III-A online stage) emit sharding specs without touching model code.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class P:
    """Declaration of one parameter tensor."""

    shape: tuple
    axes: tuple  # logical axis names (or None), len == len(shape)
    init: str = "normal"     # normal | zeros | ones
    scale: Optional[float] = None  # stddev; default 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "scale"))
def _normal(key, shape: tuple, dtype, scale: float):
    # one fused program per tensor, drawn in the target dtype: the device
    # holds the result and nothing the size of a float32 copy of it
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def _init_one(key, p: P, dtype):
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    scale = p.scale if p.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return _normal(key, tuple(p.shape), jnp.dtype(dtype), float(scale))


def is_p(x) -> bool:
    return isinstance(x, P)


def init_tree(key, spec, dtype=jnp.bfloat16):
    """Materialize a spec pytree into parameter arrays."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_p)
    keys = jax.random.split(key, len(leaves))
    arrs = [_init_one(k, p, dtype) for k, p in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrs)


def axes_tree(spec):
    """The pytree of logical-axis tuples matching ``init_tree``'s output."""
    return jax.tree.map(lambda p: p.axes, spec, is_leaf=is_p)


def abstract_tree(spec, dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins (no allocation) — used by the dry-run."""
    return jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
                        spec, is_leaf=is_p)


def param_count(spec) -> int:
    leaves = jax.tree.leaves(spec, is_leaf=is_p)
    return sum(math.prod(p.shape) for p in leaves)


def stack(spec, n: int, axis_name: str = "layers"):
    """Stack a per-layer spec ``n`` times along a new leading axis (for scan)."""
    def one(p: P) -> P:
        return P((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale)
    return jax.tree.map(one, spec, is_leaf=is_p)


__all__ = ["P", "init_tree", "axes_tree", "abstract_tree", "param_count",
           "stack", "is_p"]
