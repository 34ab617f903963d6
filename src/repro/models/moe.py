"""MoE block with MixServe's hybrid TP-EP layout and fused AR-A2A comm.

Four execution paths, selected by the ShardingPlan (see partitioner.make_plan):

  local      no mesh — single-device oracle (dispatch/compute/combine in-core)
  mixserve   hybrid TP-EP: experts sharded over the EP ("data") axis, expert
             FFN sharded over the TP ("model") axis.
             comm_algo == "fused"/"sync": the paper's RS-A2A-AG — the A2A rides
             the inter-node wire on 1/d_TP-sharded hidden states (Alg. 1-2).
             comm_algo == "unfused": Tutel-style — full-width A2A + AR.
  dp_ep      vLLM-style pure EP: experts sharded over (data x model); tokens
             sliced over the model axis (EP≡DP among experts), full-width A2A.
  pure_tp    no EP: every device holds all experts TP-sharded; AR only.

All paths share routing/dispatch/combine numerics, so with ample capacity they
are numerically equivalent — tests/test_moe.py asserts this on a CPU mesh.

Dispatch modes (``ShardingPlan.dispatch_mode`` / the ``dispatch`` argument):

  capacity   classic fixed (E, C, h) buffers, C = capacity_for(T, ...).
             C depends on the TOTAL token count, so a token's MoE output can
             change with batch composition (different slots get dropped) —
             the bucketed-prefill-vs-full-forward divergence.  Kept for
             training, where the capacity bound is the load-balancing
             contract.
  dropless   sort the (token, k) slots by expert into a ragged (T*k, h)
             buffer with an ``expert_offsets`` (E+1,) prefix-sum and run the
             expert FFN as a grouped GEMM over segments (kernels.moe_gemm.
             grouped_gemm).  No capacity, no drops, no zero padding: every
             slot's result depends only on (token row, expert), so MoE
             outputs are count-independent — prefill buckets, decode steps
             and the full forward agree exactly.  Expert compute volume is
             T*k rows instead of E*C.  Under EP the ranks first exchange
             per-rank counts (a small int32 A2A), then the ragged token A2A
             runs on worst-case-sized but mostly-empty buffers; the fused
             RS-A2A-AG collective order is preserved verbatim.
  auto       the default everywhere: resolves to dropless for inference
             plans; training (train_step.loss_fn) pins capacity.

Kernelization: the ShardingPlan carries a ``KernelPolicy``
(repro.kernels.policy) selecting which Pallas kernels replace the jnp
bodies on BOTH the local and the distributed (shard_map) paths:
  topk_gate      fused softmax+top-k router gate   (route_topk)
  fused_permute  single-gather token permute / unpermute+weighted-combine
                 (scatter_to_buffers / gather_from_buffers) instead of the
                 repeat + zeros + scatter-add / gather + reduce HBM traffic
  moe_gemm       MXU-tiled grouped expert GEMM     (expert_ffn)
The jnp bodies remain the oracles; tests/test_kernel_integration.py asserts
policy-on == policy-off to allclose, locally and on a CPU mesh.

TPU adaptation note (DESIGN.md §2): the paper's async isend/irecv rounds
become XLA async collectives; what we encode is the *communication structure*
(volume and axis placement), which is the dominant term of Eq. 13.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.cost_model import EP_OVERLAP_OFF, EpOverlap, cap_rows_for
from repro.core.partitioner import NULL_PLAN, ShardingPlan
from repro.kernels.policy import NULL_POLICY, KernelPolicy
from repro.models.layers import activate, rms_norm
from repro.models.param import P

# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------

def moe_spec(cfg: ModelConfig) -> dict:
    h, e, de = cfg.d_model, cfg.n_experts, cfg.d_expert
    gated = cfg.activation in ("swiglu", "geglu")
    spec = {
        "norm": P((h,), ("embed",), init="zeros"),
        "router": P((h, e), ("embed", None), scale=0.02),
        "w_in": P((e, h, de), ("expert", "embed", "expert_ffn")),
        "w_out": P((e, de, h), ("expert", "expert_ffn", "embed")),
    }
    if gated:
        spec["w_gate"] = P((e, h, de), ("expert", "embed", "expert_ffn"))
    if cfg.n_shared_experts:
        ds = cfg.d_expert * cfg.n_shared_experts
        spec["shared_in"] = P((h, ds), ("embed", "ffn"))
        spec["shared_out"] = P((ds, h), ("ffn", "embed"))
        if gated:
            spec["shared_gate"] = P((h, ds), ("embed", "ffn"))
    return spec


# ---------------------------------------------------------------------------
# Routing (top-k, capacity, sort-based position assignment)
# ---------------------------------------------------------------------------

def route_topk(logits, k: int, renorm: bool = True,
               use_kernel: bool = False):
    """logits: (T, E) -> (idx (T,k), weights (T,k), aux_loss scalar).

    ``use_kernel=True`` routes through the fused Pallas softmax+top-k gate
    (repro.kernels.topk_gate) — the TPU hot path; the jnp path is the
    oracle.  Both return identical (idx, weights) (tests/test_kernels.py).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if use_kernel:
        from repro.kernels import ops as _kops
        top_p, top_i = _kops.topk_gate(logits, k, renorm=renorm)
        top_p = top_p.astype(logits.dtype)
    else:
        top_p, top_i = jax.lax.top_k(probs, k)
        if renorm:
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        top_p = top_p.astype(logits.dtype)
    # Switch-Transformer load-balance loss: E * sum_e f_e * P_e
    e = logits.shape[-1]
    f = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / jnp.maximum(top_i.size, 1)
    p_mean = probs.mean(0)
    aux = e * jnp.sum(f * p_mean)
    return top_i, top_p, aux


def positions_in_expert(flat_e, n_experts: int):
    """Rank of each (token, k) slot within its expert, via stable sort —
    O(N log N), no (N, E) one-hot materialization."""
    n = flat_e.shape[0]
    # Known-risk sort, deliberately kept: single consumer chain (the
    # gather below), no interpret-mode pallas_call sibling to trigger the
    # R1 fusion-duplication miscompile, and the result is pinned bitwise
    # against the one-hot oracle in the sharded subprocess lanes.
    # Revisit if the capacity path ever feeds a Pallas kernel directly.
    order = jnp.argsort(flat_e, stable=True)  # repro-lint: disable=R1
    sorted_e = flat_e[order]
    ar = jnp.arange(n, dtype=jnp.int32)
    new_seg = jnp.concatenate([jnp.ones((1,), bool),
                               sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(new_seg, ar, 0))
    pos_sorted = ar - seg_start
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def capacity_for(tokens: int, k: int, n_experts: int, cf: float) -> int:
    """Per-expert buffer capacity.

    Aligned up to a multiple of 8 for TPU layouts ONLY once past 8 — a hard
    floor of 8 inflated decode-time buffers up to 8x (8 tokens x top-6 over
    160 experts needs capacity 1, not 8), and every dispatch/combine
    collective scales with E x capacity (§Perf pair-2 iteration)."""
    c = int(math.ceil(tokens * k * cf / max(n_experts, 1)))
    if c <= 8:
        return max(1, c)
    return -(-c // 8) * 8


@dataclasses.dataclass
class DispatchInfo:
    flat_e: jax.Array     # (T*k,) expert id per slot
    pos: jax.Array        # (T*k,) clipped position within expert buffer
    keep: jax.Array       # (T*k,) bool, False => dropped (over capacity)
    weights: jax.Array    # (T*k,)
    capacity: int


def make_dispatch(idx, weights, n_experts: int, capacity: int) -> DispatchInfo:
    flat_e = idx.reshape(-1)
    pos = positions_in_expert(flat_e, n_experts)
    keep = pos < capacity
    return DispatchInfo(flat_e=flat_e, pos=jnp.minimum(pos, capacity - 1),
                        keep=keep, weights=weights.reshape(-1),
                        capacity=capacity)


def dispatch_src_tok(d: DispatchInfo, n_experts: int, t: int):
    """Inverse slot map for the fused permute kernel: (E*C,) int32 whose
    entry for flat buffer slot (e, c) is the source TOKEN id, or -1.

    Kept (token, k) slots occupy distinct (expert, position) cells, so a
    plain int32 scatter builds the inverse exactly — E*C ints instead of the
    (T*k, h) float repeat + scatter-add the jnp dispatch pays."""
    k = d.flat_e.shape[0] // t
    cells = d.flat_e * d.capacity + d.pos
    cells = jnp.where(d.keep, cells, n_experts * d.capacity)   # park drops
    inv = jnp.full((n_experts * d.capacity + 1,), -1, jnp.int32)
    tok_of_slot = (jnp.arange(d.flat_e.shape[0], dtype=jnp.int32) // k)
    return inv.at[cells].set(tok_of_slot)[:-1]


def dispatch_src_slot(d: DispatchInfo, t: int):
    """(T, k) int32 flat buffer cells per token for the fused combine
    (negative = dropped), plus the matching (T, k) weights."""
    k = d.flat_e.shape[0] // t
    cell = d.flat_e * d.capacity + d.pos
    cell = jnp.where(d.keep, cell, -1).reshape(t, k).astype(jnp.int32)
    return cell, d.weights.reshape(t, k)


def scatter_to_buffers(x, d: DispatchInfo, n_experts: int,
                       use_kernel: bool = False):
    """x: (T, h) -> (E, C, h) capacity buffers (dropped slots contribute 0).

    ``use_kernel=True`` routes through the fused Pallas permute kernel: one
    gather pass over an int32 inverse map, no (T*k, h) repeat + zero-buffer
    scatter-add HBM round trip."""
    t, h = x.shape
    if use_kernel:
        from repro.kernels import ops as _kops
        src = dispatch_src_tok(d, n_experts, t)
        return _kops.permute_tokens(x, src).reshape(n_experts, d.capacity, h)
    k = d.flat_e.shape[0] // t
    vals = jnp.repeat(x, k, axis=0) * d.keep[:, None].astype(x.dtype)
    buf = jnp.zeros((n_experts, d.capacity, h), x.dtype)
    return buf.at[d.flat_e, d.pos].add(vals)


def gather_from_buffers(buf, d: DispatchInfo, t: int,
                        use_kernel: bool = False):
    """buf: (E, C, h) -> (T, h) weighted combine.

    ``use_kernel=True`` fuses the gather and the weighted k-way reduce into
    the Pallas unpermute kernel (single pass, f32 accumulation)."""
    if use_kernel:
        from repro.kernels import ops as _kops
        slot, w = dispatch_src_slot(d, t)
        return _kops.unpermute_tokens(
            buf.reshape(-1, buf.shape[-1]), slot, w).astype(buf.dtype)
    vals = buf[d.flat_e, d.pos]
    vals = vals * (d.weights * d.keep.astype(d.weights.dtype))[:, None]
    k = d.flat_e.shape[0] // t
    return vals.reshape(t, k, -1).sum(1)


# ---------------------------------------------------------------------------
# Dropless (ragged, count-independent) dispatch
# ---------------------------------------------------------------------------

def resolve_dispatch(mode: Optional[str]) -> str:
    """"auto"/None -> "dropless" (the inference default); validates others."""
    if mode in (None, "auto"):
        return "dropless"
    if mode not in ("capacity", "dropless"):
        raise ValueError(f"unknown dispatch mode {mode!r} "
                         "(want 'auto' | 'capacity' | 'dropless')")
    return mode


@dataclasses.dataclass
class DroplessInfo:
    """Sorted-slot dispatch: all shapes static, values dynamic."""
    order: jax.Array      # (T*k,) slot id of sorted row i (by expert, stable)
    inv: jax.Array        # (T*k,) sorted row of slot f (inverse of order)
    counts: jax.Array     # (E,) tokens routed to each expert
    offsets: jax.Array    # (E+1,) prefix-sum: expert e owns rows [e, e+1)
    weights: jax.Array    # (T*k,) routing weights per slot


def make_dropless(idx, weights, n_experts: int) -> DroplessInfo:
    flat_e = idx.reshape(-1)
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return DroplessInfo(order=order, inv=inv, counts=counts,
                        offsets=offsets, weights=weights.reshape(-1))


def gather_rows(x, src, use_kernel: bool = False, total=None,
                seg_stride: Optional[int] = None):
    """x (M, h), src (N,) int32 -> (N, h); src < 0 yields a 0 row.

    ``total`` (dynamic): valid-prefix row count(s) — a scalar for one
    prefix over the whole buffer, or a per-segment vector with segments at
    ``seg_stride`` intervals (the EP send layout).  Routes to the
    segment-aware ragged permute kernel that skips empty tiles; validity
    layout is metadata only — ``src`` must already be -1 outside it."""
    if use_kernel:
        from repro.kernels import ops as _kops
        if total is not None:
            return _kops.permute_tokens_ragged(x, src, total,
                                               seg_stride=seg_stride)
        return _kops.permute_tokens(x, src)
    from repro.kernels import ref as _kref
    return _kref.permute_tokens_ref(x, src)


def combine_rows(buf, src_slot, weights, use_kernel: bool = False):
    """buf (M, h), src_slot (T, k), weights (T, k) -> (T, h) weighted sum."""
    if use_kernel:
        from repro.kernels import ops as _kops
        return _kops.unpermute_tokens(buf, src_slot, weights)
    from repro.kernels import ref as _kref
    return _kref.unpermute_tokens_ref(buf, src_slot, weights)


EXPERT_WEIGHTS = ("w_in", "w_gate", "w_out")


def reads_stack(plan: ShardingPlan) -> bool:
    """Whether the MoE layer scan hands ``grouped_gemm`` the layer-stacked
    expert weights (``EXPERT_WEIGHTS``) and a ``"layer"`` index in place of
    one layer's slice: the local dropless path with the kernel on."""
    return (not plan.enabled and plan.kernels.moe_gemm
            and resolve_dispatch(plan.dispatch_mode) == "dropless")


def grouped_ffn(p, xs, offsets, cfg: ModelConfig, use_kernel: bool = False):
    """Expert FFN over a ragged buffer.  xs (N, h) sorted by expert,
    offsets (E_local+1,) -> (N, h).  Rows at/after offsets[-1] are
    unspecified (never read).  Partial sum over TP shards when de' < de,
    exactly like ``expert_ffn``.  With a ``"layer"`` entry in ``p`` (the
    kernel path only, ``reads_stack``) the expert weights are the
    (L, E, h, de) layer stacks and the kernel reads that layer in place."""
    if use_kernel:
        from repro.kernels import ops as _kops
        gg = functools.partial(_kops.grouped_gemm, layer=p.get("layer"))
    else:
        from repro.kernels import ref as _kref
        gg = _kref.grouped_gemm_ref
    up = gg(xs, p["w_in"], offsets)
    if "w_gate" in p:
        mid = activate(gg(xs, p["w_gate"], offsets), up, cfg.activation)
    else:
        mid = activate(up, up, cfg.activation)
    return gg(mid, p["w_out"], offsets)


# ---------------------------------------------------------------------------
# Expert FFN on capacity buffers
# ---------------------------------------------------------------------------

def expert_ffn(p, buf, cfg: ModelConfig, use_kernel: bool = False):
    """buf: (E_local, C', h) with w_in (E_local, h, de') -> (E_local, C', h).

    Output is a *partial sum* over the expert_ffn (TP) shards when de' < de.
    ``use_kernel=True`` runs the grouped GEMMs through the Pallas
    ``moe_gemm`` kernel (MXU-tiled) instead of jnp.einsum.
    """
    if use_kernel:
        from repro.kernels import ops as _kops
        up = _kops.moe_gemm(buf, p["w_in"])
        if "w_gate" in p:
            mid = activate(_kops.moe_gemm(buf, p["w_gate"]), up,
                           cfg.activation)
        else:
            mid = activate(up, up, cfg.activation)
        return _kops.moe_gemm(mid, p["w_out"])
    up = jnp.einsum("ech,ehd->ecd", buf, p["w_in"])
    if "w_gate" in p:
        gate = jnp.einsum("ech,ehd->ecd", buf, p["w_gate"])
        mid = activate(gate, up, cfg.activation)
    else:
        mid = activate(up, up, cfg.activation)
    return jnp.einsum("ecd,edh->ech", mid, p["w_out"])


def shared_expert_ffn(p, x, cfg: ModelConfig):
    """Shared experts on local tokens; returns partial sums under TP."""
    up = x @ p["shared_in"]
    if "shared_gate" in p:
        mid = activate(x @ p["shared_gate"], up, cfg.activation)
    else:
        mid = activate(up, up, cfg.activation)
    return mid @ p["shared_out"]


# ---------------------------------------------------------------------------
# Local (single-device) oracle
# ---------------------------------------------------------------------------

def moe_local(p, x, cfg: ModelConfig, cf: Optional[float] = None,
              use_kernels: bool = False,
              policy: Optional[KernelPolicy] = None,
              dispatch: Optional[str] = None,
              with_stats: bool = False):
    """x: (b, s, h).  Returns (out, aux_loss)
    [+ per-expert routed counts (E,) int32 when ``with_stats``].

    ``policy`` selects the Pallas kernels per stage (interpret mode on CPU;
    native on TPU); ``use_kernels=True`` is the legacy shorthand for
    ``KernelPolicy.all_on()``.  ``dispatch`` ("auto" -> dropless) picks the
    buffer scheme; ``cf`` only applies to capacity mode."""
    if policy is None:
        policy = KernelPolicy.all_on() if use_kernels else NULL_POLICY
    dispatch = resolve_dispatch(dispatch)
    b, s, h = x.shape
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    tok = xn.reshape(-1, h)
    t = tok.shape[0]
    k = cfg.top_k
    idx, w, aux = route_topk(tok @ p["router"], k,
                             use_kernel=policy.topk_gate)
    if dispatch == "dropless":
        dl = make_dropless(idx, w, cfg.n_experts)
        xs = gather_rows(tok, dl.order // k,               # (T*k, h) sorted
                         use_kernel=policy.fused_permute)
        ys = grouped_ffn(p, xs, dl.offsets, cfg, use_kernel=policy.moe_gemm)
        out = combine_rows(ys, dl.inv.reshape(t, k), w.reshape(t, k),
                           use_kernel=policy.fused_permute)
    else:
        if cf is None:
            cf = cfg.capacity_factor
        cap = capacity_for(t, k, cfg.n_experts, cf)
        d = make_dispatch(idx, w, cfg.n_experts, cap)
        buf = scatter_to_buffers(tok, d, cfg.n_experts,
                                 use_kernel=policy.fused_permute)
        out_buf = expert_ffn(p, buf, cfg, use_kernel=policy.moe_gemm)
        out = gather_from_buffers(out_buf, d, t,
                                  use_kernel=policy.fused_permute)
    if cfg.n_shared_experts:
        out = out + shared_expert_ffn(p, tok, cfg)
    out = out.reshape(b, s, h).astype(x.dtype)
    if with_stats:
        counts = jnp.zeros((cfg.n_experts,), jnp.int32).at[
            idx.reshape(-1)].add(1)
        return out, aux, counts
    return out, aux


# ---------------------------------------------------------------------------
# Distributed paths (shard_map)
# ---------------------------------------------------------------------------

def _axis_index(axes: tuple):
    if not axes:
        return 0
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axis_size(axes: tuple):
    s = 1
    for a in axes:
        s *= jax.lax.axis_size(a)
    return s


def _routed_counts_stat(idx, e_global: int, mesh_axes: tuple, tp_axes: tuple,
                        token_sliced: bool):
    """(E,) int32 global routed-slot counts, replicated (observability).

    Tokens are sharded over the non-TP mesh axes (and over tp too when
    token-sliced); sum those partials, keep the TP replication."""
    c = jnp.zeros((e_global,), jnp.int32).at[idx.reshape(-1)].add(1)
    cnt_axes = tuple(a for a in mesh_axes if a not in tuple(tp_axes))
    if token_sliced:
        cnt_axes = cnt_axes + tuple(a for a in tp_axes if a not in cnt_axes)
    if cnt_axes:
        c = jax.lax.psum(c, cnt_axes)
    return c


def _moe_shard_fn(p, x, *, cfg: ModelConfig, tp_axes, ep_axes, comm_algo,
                  token_sliced: bool, cf: float, mesh_axes: tuple = (),
                  policy: KernelPolicy = NULL_POLICY,
                  with_stats: bool = False):
    """Per-device body.  x: (b_loc, s, h) — replicated across tp_axes.

    Returns (out (b_loc, s, h), aux scalar) — out replicated across tp_axes
    — plus (E,) int32 routed counts when ``with_stats``.
    ``policy`` kernelizes the per-device compute (gate, permute, expert
    GEMMs); the collectives between them are untouched.
    """
    b, s, h = x.shape
    tp = _axis_size(tp_axes) if tp_axes else 1
    ep = _axis_size(ep_axes) if ep_axes else 1
    e_global = cfg.n_experts
    e_local = e_global // max(ep, 1)

    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    tok_full = xn.reshape(-1, h)

    t_real = tok_full.shape[0]
    if token_sliced and tp > 1:
        # pure-EP (vLLM DP+EP): the TP group's replicated tokens are sliced
        # along the *token* axis so each (data x model) device is its own
        # EP/DP rank — "EP is essentially equivalent to DP among the experts".
        # Pad to a multiple of tp (odd counts occur in tests/serving).
        pad = (-t_real) % tp
        if pad:
            tok_full = jnp.pad(tok_full, ((0, pad), (0, 0)))
        t_loc = tok_full.shape[0] // tp
        tok = jax.lax.dynamic_slice_in_dim(
            tok_full, _axis_index(tp_axes) * t_loc, t_loc, axis=0)
    else:
        tok = tok_full
    t = tok.shape[0]

    idx, w, aux = route_topk(tok @ p["router"], cfg.top_k,
                             use_kernel=policy.topk_gate)
    cap = capacity_for(t, cfg.top_k, e_global, cf)
    d = make_dispatch(idx, w, e_global, cap)

    fused = (comm_algo in ("fused", "sync")) and tp > 1 and ep > 1 \
        and not token_sliced

    # ---------------- dispatch ----------------
    if fused:
        # Fused AG-Dispatch (Alg. 2): scatter only the LOCAL 1/tp hidden
        # shard; the inter-node A2A then moves 1/tp of the volume, and the
        # intra-node AG reconstructs full width (overlapped by XLA).
        hs = h // tp
        tok_shard = jax.lax.dynamic_slice_in_dim(
            tok, _axis_index(tp_axes) * hs, hs, axis=1)
        buf = scatter_to_buffers(tok_shard, d, e_global,
                                 use_kernel=policy.fused_permute)  # (E,C,h/tp)
    else:
        buf = scatter_to_buffers(tok, d, e_global,
                                 use_kernel=policy.fused_permute)  # (E,C,h)

    if ep > 1:
        buf = buf.reshape(ep, e_local, cap, buf.shape[-1])
        ax = ep_axes if len(ep_axes) > 1 else ep_axes[0]
        buf = jax.lax.all_to_all(buf, ax, split_axis=0, concat_axis=0,
                                 tiled=False)
        # (ep, e_local, C, h') — dim 0 now indexes the source EP rank
        buf = buf.reshape(ep, e_local, cap, buf.shape[-1])
    else:
        buf = buf.reshape(1, e_global, cap, buf.shape[-1])

    if fused:
        buf = jax.lax.all_gather(buf, tp_axes, axis=-1, tiled=True)  # full h

    # ---------------- expert compute ----------------
    if ep > 1:
        # (ep, e_local, C, h) -> (e_local, ep*C, h): one GEMM batch per
        # local expert over the buffers received from every source rank.
        comp = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, h)
    else:
        comp = buf.reshape(e_global, cap, h)
    out_buf = expert_ffn(p, comp, cfg,     # partial over tp when sharded
                         use_kernel=policy.moe_gemm)

    # ---------------- combine ----------------
    if ep > 1:
        out_buf = out_buf.reshape(e_local, ep, cap, h).transpose(1, 0, 2, 3)

    # Shared experts: under token slicing the TP ranks hold *different*
    # tokens, so partial-width products cannot be psummed per slice — compute
    # them on the full replicated token set instead (vLLM DP+EP does the
    # same: shared experts stay TP with a standard AR).
    shared_partial = None
    if cfg.n_shared_experts:
        shared_partial = shared_expert_ffn(
            p, tok_full if token_sliced else tok, cfg)

    if fused:
        # Fused RS-Combine (Alg. 1): reduce-scatter the TP-partial expert
        # outputs down to 1/tp width, A2A back at 1/tp volume, weighted
        # combine, and a single epilogue AG restores full width.
        out_buf = jax.lax.psum_scatter(out_buf, tp_axes, scatter_dimension=3,
                                       tiled=True)            # (ep,eL,C,h/tp)
        ax = ep_axes if len(ep_axes) > 1 else ep_axes[0]
        out_buf = jax.lax.all_to_all(out_buf, ax, split_axis=0, concat_axis=0)
        out_buf = out_buf.reshape(e_global, cap, h // tp)
        out_tok = gather_from_buffers(out_buf, d, t,          # (T, h/tp)
                                      use_kernel=policy.fused_permute)
        if shared_partial is not None:
            # fold the shared-expert partial into the same epilogue: RS it to
            # 1/tp width and add before the single AG (beyond-paper fusion).
            out_tok = out_tok + jax.lax.psum_scatter(
                shared_partial, tp_axes, scatter_dimension=1, tiled=True)
        out_tok = jax.lax.all_gather(out_tok, tp_axes, axis=-1, tiled=True)
    else:
        if tp > 1 and not token_sliced:
            out_buf = jax.lax.psum(out_buf, tp_axes)          # AR (unfused)
        if ep > 1:
            ax = ep_axes if len(ep_axes) > 1 else ep_axes[0]
            out_buf = jax.lax.all_to_all(out_buf, ax, split_axis=0,
                                         concat_axis=0)
            out_buf = out_buf.reshape(e_global, cap, h)
        else:
            out_buf = out_buf.reshape(e_global, cap, h)
        out_tok = gather_from_buffers(out_buf, d, t,
                                      use_kernel=policy.fused_permute)
        if token_sliced and tp > 1:
            # undo the token slice: gather the TP group's token shards back
            out_tok = jax.lax.all_gather(out_tok, tp_axes, axis=0, tiled=True)
            out_tok = out_tok[:t_real]          # drop slicing pad
        if shared_partial is not None:
            if tp > 1:
                shared_partial = jax.lax.psum(shared_partial, tp_axes)
            out_tok = out_tok + shared_partial

    out = out_tok.reshape(b, s, h).astype(x.dtype)
    if mesh_axes:
        aux = jax.lax.pmean(aux, mesh_axes)  # replicate for the P() out_spec
    if with_stats:
        return out, aux, _routed_counts_stat(idx, e_global, mesh_axes,
                                             tp_axes, token_sliced)
    return out, aux


def _moe_shard_dropless_fn(p, x, *, cfg: ModelConfig, tp_axes, ep_axes,
                           comm_algo, token_sliced: bool,
                           mesh_axes: tuple = (),
                           policy: KernelPolicy = NULL_POLICY,
                           ep_overlap: Optional[EpOverlap] = None,
                           with_stats: bool = False):
    """Per-device dropless body.  x: (b_loc, s, h) — replicated across
    tp_axes.  Returns (out (b_loc, s, h), aux scalar)
    [+ counts (E,) int32 when ``with_stats``].

    EP exchange without capacity padding: ranks first A2A their per-expert
    slot counts (an (ep, e_local) int32 — bytes, not activations), then A2A
    ragged token buffers.  The fused RS-A2A-AG path keeps the paper's
    collective order: the dispatch A2A rides on 1/tp-sharded hidden states,
    an AG restores full width before the expert GEMMs, and the combine
    reduces-scatters back to 1/tp before the return A2A and a single
    epilogue AG (Alg. 1-2).

    ``ep_overlap`` selects the exchange *schedule* (values never change):

    * ``None`` / ``EP_OVERLAP_OFF`` — monolithic: one dispatch A2A at the
      worst-case per-destination extent ``S = T_local*k`` (every rank could
      receive everything), one grouped GEMM, one combine A2A, strictly
      serial.
    * otherwise — micro-chunked pipeline: the token batch splits into C
      equal chunks; ALL chunks' counts + dispatch A2As are issued before
      any chunk's grouped GEMM, and each chunk's combine A2A is issued as
      soon as its GEMM finishes, so chunk i's dispatch rides the wire under
      chunk i-1's GEMM and chunk i-2's combine (XLA's async scheduler
      overlaps the independent ops).  Each chunk's exchange is
      **count-bounded**: a static per-rank row cap ``S = cap_rows_for(...)``
      priced from the routing distribution replaces the worst-case extent,
      shrinking A2A bytes by ~S*ep/n.  If any (source, dest) segment in the
      collective group overflows the cap, THAT chunk recomputes at the
      worst-case extent (a rank-uniform ``lax.cond`` — every peer takes the
      same branch), so the result is bit-identical to the monolithic
      schedule in all cases."""
    b, s, h = x.shape
    tp = _axis_size(tp_axes) if tp_axes else 1
    ep = _axis_size(ep_axes) if ep_axes else 1
    e_global = cfg.n_experts
    e_local = e_global // max(ep, 1)
    k = cfg.top_k

    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    tok_full = xn.reshape(-1, h)

    t_real = tok_full.shape[0]
    if token_sliced and tp > 1:
        pad = (-t_real) % tp
        if pad:
            tok_full = jnp.pad(tok_full, ((0, pad), (0, 0)))
        t_loc = tok_full.shape[0] // tp
        tok = jax.lax.dynamic_slice_in_dim(
            tok_full, _axis_index(tp_axes) * t_loc, t_loc, axis=0)
    else:
        tok = tok_full
    t = tok.shape[0]
    n = t * k

    idx, w, aux = route_topk(tok @ p["router"], k,
                             use_kernel=policy.topk_gate)

    fused = (comm_algo in ("fused", "sync")) and tp > 1 and ep > 1 \
        and not token_sliced

    def _ret(out_tok):
        out = out_tok.reshape(b, s, h).astype(x.dtype)
        nonlocal aux
        if mesh_axes:
            aux = jax.lax.pmean(aux, mesh_axes)
        if with_stats:
            return out, aux, _routed_counts_stat(idx, e_global, mesh_axes,
                                                 tp_axes, token_sliced)
        return out, aux

    if ep == 1:
        # no EP exchange: the local dropless pipeline, with the usual TP
        # partial-sum reduction over the expert_ffn shards.
        dl = make_dropless(idx, w, e_global)
        xs = gather_rows(tok, dl.order // k,
                         use_kernel=policy.fused_permute)
        ys = grouped_ffn(p, xs, dl.offsets, cfg, use_kernel=policy.moe_gemm)
        if tp > 1 and not token_sliced:
            ys = jax.lax.psum(ys, tp_axes)
        out_tok = combine_rows(ys, dl.inv.reshape(t, k), w.reshape(t, k),
                               use_kernel=policy.fused_permute)
        if token_sliced and tp > 1:
            out_tok = jax.lax.all_gather(out_tok, tp_axes, axis=0,
                                         tiled=True)[:t_real]
        if cfg.n_shared_experts:
            sp = shared_expert_ffn(p, tok_full if token_sliced else tok, cfg)
            if tp > 1:          # shared FFN weights are TP-sharded partials
                sp = jax.lax.psum(sp, tp_axes)
            out_tok = out_tok + sp[:out_tok.shape[0]]
        return _ret(out_tok)

    ax = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    ovl = ep_overlap if ep_overlap is not None else EP_OVERLAP_OFF
    # Micro-chunking: C equal token slices (C=1 = the monolithic schedule).
    # gcd keeps chunk shapes static and equal for any local token count.
    C = math.gcd(ovl.chunks, t) if ovl.chunks > 1 else 1
    t_c = t // C
    n_c = t_c * k                  # worst-case per-rank rows for one chunk
    cap = cap_rows_for(n_c, ep, ovl)   # count-bounded per-rank extent

    if fused:
        hs = h // tp
        tok_payload = jax.lax.dynamic_slice_in_dim(
            tok, _axis_index(tp_axes) * hs, hs, axis=1)   # (t, h/tp)
    else:
        tok_payload = tok

    # every axis that participates in this block's collectives must take the
    # same cond branch; counts are TP-replicated so the pmax is uniform.
    ovf_axes = tuple(dict.fromkeys(tuple(ep_axes) + tuple(tp_axes)))

    def send_recv(tok_c, dl, S):
        """Count-bounded dispatch A2A for one chunk at static extent S:
        rank r's rows are the sorted segment [offsets[r*e_local],
        offsets[(r+1)*e_local]) truncated to its first S rows."""
        rank_off = dl.offsets[::e_local]                  # (ep+1,)
        rank_cnt = rank_off[1:] - rank_off[:-1]           # (ep,)
        rank_eff = jnp.minimum(rank_cnt, S)
        i_in = jnp.arange(S, dtype=jnp.int32)[None, :]    # (1, S)
        p_sorted = rank_off[:-1, None] + i_in             # (ep, S)
        valid_send = i_in < rank_eff[:, None]
        src_tok_send = jnp.where(
            valid_send, dl.order[jnp.minimum(p_sorted, n_c - 1)] // k, -1)
        # per-destination-rank prefixes, NOT one contiguous prefix: rank r's
        # rows live at [r*S, r*S + rank_eff[r]), so the elision metadata is
        # the (ep,) count vector with stride S
        send = gather_rows(tok_c, src_tok_send.reshape(-1),
                           use_kernel=policy.fused_permute,
                           total=rank_eff, seg_stride=S)  # (ep*S, h')
        send = send.reshape(ep, S, -1)
        recv = jax.lax.all_to_all(send, ax, split_axis=0, concat_axis=0,
                                  tiled=False)            # (ep, S, h')
        recv = recv.reshape(ep, S, send.shape[-1])
        if fused:
            recv = jax.lax.all_gather(recv, tp_axes, axis=-1, tiled=True)
        return recv

    def expert_phase(recv, recv_counts, S):
        """Regroup received rows by local expert (closed form from the count
        prefix-sums — no sort), grouped FFN, TP reduction, and the combine
        A2A.  comb row of recv row (s, i) = expert base (off_local[le]) +
        rows for le from earlier sources + rank of i within source s's le
        segment."""
        i_in = jnp.arange(S, dtype=jnp.int32)[None, :]    # (1, S)
        csum = jnp.cumsum(recv_counts, axis=1)            # (ep, e_local)
        # Under the row cap each sender truncated its expert-sorted segment
        # at S rows, so the *effective* per-(source, expert) counts are the
        # prefix sums clipped at S, re-diffed (matches the sender exactly).
        csum_eff = jnp.minimum(csum, S)
        cnt_eff = csum_eff - jnp.concatenate(
            [jnp.zeros((ep, 1), jnp.int32), csum_eff[:, :-1]], axis=1)
        tot_src = csum_eff[:, -1]                         # (ep,)
        le = (csum_eff[:, :, None] <= i_in[None, :, :]).sum(1)  # (ep, S)
        le = le.astype(jnp.int32)
        valid_2d = i_in < tot_src[:, None]                # (ep, S)
        counts_le = cnt_eff.sum(0)                        # (e_local,)
        off_local = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                     jnp.cumsum(counts_le).astype(jnp.int32)])
        le_c = jnp.minimum(le, e_local - 1)
        start_in_src = jnp.take_along_axis(csum_eff - cnt_eff, le_c, axis=1)
        from_earlier = jnp.take_along_axis(
            jnp.cumsum(cnt_eff, axis=0) - cnt_eff, le_c, axis=1)
        pos = off_local[le_c] + from_earlier + (i_in - start_in_src)
        valid_recv = valid_2d.reshape(-1)                 # (ep*S,)
        comb_inv = jnp.where(valid_recv, pos.reshape(-1), 0).astype(jnp.int32)
        park = jnp.where(valid_recv, comb_inv, ep * S)
        comb_src = jnp.full((ep * S + 1,), -1, jnp.int32).at[park].set(
            jnp.arange(ep * S, dtype=jnp.int32))[:-1]
        m_real = tot_src.sum()
        comb = gather_rows(recv.reshape(ep * S, -1), comb_src,
                           use_kernel=policy.fused_permute, total=m_real)
        ys = grouped_ffn(p, comb, off_local, cfg,         # partial over tp
                         use_kernel=policy.moe_gemm)
        if fused:
            ys = jax.lax.psum_scatter(ys, tp_axes, scatter_dimension=1,
                                      tiled=True)         # (M, h/tp)
        elif tp > 1 and not token_sliced:
            ys = jax.lax.psum(ys, tp_axes)
        out_recv = gather_rows(ys, jnp.where(valid_recv, comb_inv, -1),
                               use_kernel=policy.fused_permute)
        out_send = jax.lax.all_to_all(
            out_recv.reshape(ep, S, -1), ax, split_axis=0, concat_axis=0)
        return out_send.reshape(ep * S, -1)

    def combine_phase(out_send, dl, w_c, S):
        """Weighted k-way combine.  Slot f sits at sorted position
        p = inv[f]; its rank r is the segment containing p (closed-form
        prefix-sum arithmetic == searchsorted(rank_off, p, 'right') - 1),
        its exchange row r*S + (p - rank_off[r])."""
        rank_off = dl.offsets[::e_local]
        p_pos = dl.inv
        r_of = (rank_off[None, 1:] <= p_pos[:, None]).sum(1).astype(jnp.int32)
        off_in_rank = p_pos - rank_off[r_of]
        row = r_of * S + jnp.minimum(off_in_rank, S - 1)
        return combine_rows(out_send, row.reshape(t_c, k),
                            w_c.reshape(t_c, k),
                            use_kernel=policy.fused_permute)

    # ---- stage 1: per-chunk routing metadata; issue ALL counts A2As and
    # count-bounded dispatch A2As up front (nothing downstream depends on
    # them yet, so they ride the wire under later chunks' compute) ----
    stage = []
    for i in range(C):
        sl = slice(i * t_c, (i + 1) * t_c)
        dl = make_dropless(idx[sl], w[sl], e_global)
        recv_counts = jax.lax.all_to_all(
            dl.counts.reshape(ep, e_local), ax, split_axis=0, concat_axis=0,
            tiled=False)                                  # (ep, e_local)
        recv = send_recv(tok_payload[sl], dl, cap)
        if cap < n_c:
            # overflow: any (source, dest) segment beyond the cap anywhere
            # in the collective group -> this chunk recomputes at the
            # worst-case extent (rank-uniform predicate).
            rank_off = dl.offsets[::e_local]
            seg_max = jnp.max(rank_off[1:] - rank_off[:-1])
            ovf = jax.lax.pmax(seg_max, ovf_axes) > cap
        else:
            ovf = None
        stage.append((dl, recv_counts, recv, ovf, w[sl], tok_payload[sl]))

    # ---- stage 2: per-chunk grouped FFN + combine A2A (chunk i's GEMM has
    # no dependency on chunk i+1's dispatch A2A — they overlap) ----
    outs = []
    for dl, recv_counts, recv, ovf, w_c, tok_c in stage:
        out_c = combine_phase(expert_phase(recv, recv_counts, cap),
                              dl, w_c, cap)
        if ovf is not None:
            # rare path: redo dispatch->FFN->combine at worst-case extent.
            # The counts A2A is NOT redone (counts are cap-independent).
            full = jax.lax.cond(
                ovf,
                lambda tc=tok_c, d=dl, rc=recv_counts, wc=w_c: combine_phase(
                    expert_phase(send_recv(tc, d, n_c), rc, n_c), d, wc, n_c),
                lambda: jnp.zeros((t_c, out_c.shape[-1]), out_c.dtype))
            out_c = jnp.where(ovf, full, out_c)
        outs.append(out_c)
    out_tok = outs[0] if C == 1 else jnp.concatenate(outs, axis=0)

    # ---------------- epilogue (full token batch) ----------------
    shared_partial = None
    if cfg.n_shared_experts:
        shared_partial = shared_expert_ffn(
            p, tok_full if token_sliced else tok, cfg)

    if fused:
        if shared_partial is not None:
            out_tok = out_tok + jax.lax.psum_scatter(
                shared_partial, tp_axes, scatter_dimension=1, tiled=True)
        out_tok = jax.lax.all_gather(out_tok, tp_axes, axis=-1, tiled=True)
    else:
        if token_sliced and tp > 1:
            out_tok = jax.lax.all_gather(out_tok, tp_axes, axis=0,
                                         tiled=True)[:t_real]
        if shared_partial is not None:
            if tp > 1:          # shared FFN weights are TP-sharded partials
                shared_partial = jax.lax.psum(shared_partial, tp_axes)
            out_tok = out_tok + shared_partial[:out_tok.shape[0]]

    return _ret(out_tok)


def moe_block(p, x, cfg: ModelConfig, plan: ShardingPlan = NULL_PLAN, *,
              cf: Optional[float] = None, dispatch: Optional[str] = None,
              with_stats: bool = False):
    """The MoE block.  x: (b, s, h) -> (out, aux_loss)
    [+ per-expert routed counts (E,) int32, replicated, when ``with_stats``
    — the expert-load observability feed, on every path].

    ``plan.kernels`` (a KernelPolicy) decides which stages run as Pallas
    kernels.  ``plan.ep_overlap`` (an EpOverlap) selects the micro-chunked
    count-bounded EP-exchange schedule on the dropless path; None keeps the
    monolithic worst-case exchange.  ``dispatch`` overrides
    ``plan.dispatch_mode`` ("auto" resolves to dropless — see the module
    docstring); ``cf`` only applies to capacity mode, where cf=0.0 is a
    legal (degenerate) capacity factor, so only None falls back to the
    config default."""
    mode = resolve_dispatch(dispatch if dispatch is not None
                            else getattr(plan, "dispatch_mode", None))
    if cf is None:
        cf = cfg.capacity_factor
    if not plan.enabled:
        return moe_local(p, x, cfg, cf, policy=plan.kernels, dispatch=mode,
                         with_stats=with_stats)

    mesh = plan.mesh
    # dp_ep plan: ep_axes overlaps tp_axes (experts span data x model) ->
    # token-sliced pure EP; tokens replicated within the TP group are sliced
    # along the token axis instead of the hidden axis.
    token_sliced = bool(set(plan.tp_axes) & set(plan.ep_axes))
    comm_algo = "unfused" if token_sliced else plan.comm_algo

    # The shard_map body expects the canonical EP/TP layout; any outer FSDP
    # storage sharding (embed->data) is converted at the shard_map boundary
    # (= the FSDP gather-on-use).  Pin the layout by dropping the fsdp rule.
    import dataclasses as _dc
    moe_plan = _dc.replace(plan, rules={**plan.rules, "embed": None})
    x_spec = moe_plan.spec(("batch", "seq", "embed"))
    p_axes = {k: v.axes for k, v in moe_spec(cfg).items() if k in p}
    p_specs = {k: moe_plan.spec(ax) for k, ax in p_axes.items()}

    ep = plan.axis_size(plan.ep_axes)
    if ep > 1 and cfg.n_experts % ep:
        raise ValueError(
            f"{cfg.name}: n_experts={cfg.n_experts} not divisible by "
            f"EP degree {ep} — pick a different plan (analyzer enforces this)")

    if mode == "dropless":
        fn = functools.partial(
            _moe_shard_dropless_fn, cfg=cfg, tp_axes=plan.tp_axes,
            ep_axes=plan.ep_axes, comm_algo=comm_algo,
            token_sliced=token_sliced, mesh_axes=tuple(mesh.axis_names),
            policy=plan.kernels, ep_overlap=plan.ep_overlap,
            with_stats=with_stats)
    else:
        fn = functools.partial(
            _moe_shard_fn, cfg=cfg, tp_axes=plan.tp_axes,
            ep_axes=plan.ep_axes, comm_algo=comm_algo,
            token_sliced=token_sliced, cf=cf,
            mesh_axes=tuple(mesh.axis_names), policy=plan.kernels,
            with_stats=with_stats)

    out_specs = (x_spec, PartitionSpec())
    if with_stats:
        out_specs = out_specs + (PartitionSpec(),)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=out_specs,
        check_vma=False,
    )(p, x)


__all__ = [
    "moe_spec", "moe_block", "moe_local", "route_topk", "make_dispatch",
    "scatter_to_buffers", "gather_from_buffers", "expert_ffn",
    "capacity_for", "positions_in_expert", "DispatchInfo",
    "dispatch_src_tok", "dispatch_src_slot",
    "resolve_dispatch", "DroplessInfo", "make_dropless", "gather_rows",
    "combine_rows", "grouped_ffn", "EXPERT_WEIGHTS", "reads_stack",
]
