"""R1: no sorts inside shard_map bodies."""

from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.astutils import normalized
from repro.analysis.lint import Finding

_SORT_FNS = {"argsort", "sort", "searchsorted", "lexsort"}
_SORT_MODULES = {"jnp", "np", "lax", "jax"}


class SortInShardMapRule:
    """No ``sort``/``argsort``/``searchsorted`` inside shard_map bodies.

    Incident (ROADMAP "Notes for kernel/shard_map work"): XLA:CPU
    miscompiled an ``argsort`` whose consumers included interpret-mode
    pallas calls inside ``shard_map`` — wrong only when the sort was
    fusion-duplicated, so no unit test caught it until the sharded
    end-to-end lane diverged.  The EP regroup permutation is therefore
    computed in closed form from count prefix-sums (see
    ``models.moe._moe_shard_dropless_fn.expert_phase``); prefer index
    arithmetic over sorts in shard_map bodies.

    The rule flags calls to ``jnp.argsort`` / ``jnp.sort`` /
    ``jnp.searchsorted`` / ``jnp.lexsort`` / ``lax.sort`` in any function
    passed to ``shard_map`` or reachable from one through the repo call
    graph.  Audited survivors (e.g. the per-chunk local token sort whose
    single consumer chain is regression-tested bitwise against the
    oracle) carry ``# repro-lint: disable=R1`` with a justification.
    """

    id = "R1"
    title = "no sort/argsort/searchsorted inside shard_map bodies"

    def check(self, ctx) -> Iterable[Finding]:
        idx = ctx.index
        scope = idx.reachable(idx.shard_roots)
        for fi in scope.values():
            mod = fi.module
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                name = normalized(mod, node.func)
                if name is None:
                    continue
                head, _, last = name.rpartition(".")
                if last in _SORT_FNS and head.split(".")[0] in _SORT_MODULES:
                    yield Finding(
                        self.id, str(mod.path), node.lineno, node.col_offset,
                        f"`{name}` inside a shard_map body ({fi.qualname}): "
                        "XLA:CPU has miscompiled fusion-duplicated sorts "
                        "here — use closed-form index arithmetic from count "
                        "prefix-sums (see docs/analysis.md#r1)",
                        symbol=fi.qualname)

    # The historical pattern, verbatim shape: an argsort computed inside a
    # shard_map body whose consumer is a (pallas-backed) gather.
    FIXTURE_BAD = '''
import jax
import jax.numpy as jnp
from jax import shard_map


def _regroup(x, flat_e):
    order = jnp.argsort(flat_e, stable=True)   # the miscompiled pattern
    return x[order]


def _body(x, e):
    return _regroup(x, e)


def run(mesh, x, e):
    return shard_map(_body, mesh=mesh, in_specs=None, out_specs=None)(x, e)
'''

    FIXTURE_GOOD = '''
import jax
import jax.numpy as jnp
from jax import shard_map


def _regroup(x, counts):
    # closed form from count prefix-sums -- no sort
    offsets = jnp.cumsum(counts)
    return x, offsets


def _body(x, counts):
    return _regroup(x, counts)


def host_side_sort(v):
    return jnp.argsort(v)   # fine: not reachable from a shard_map body


def run(mesh, x, counts):
    return shard_map(_body, mesh=mesh, in_specs=None, out_specs=None)(x, counts)
'''


RULE = SortInShardMapRule()
