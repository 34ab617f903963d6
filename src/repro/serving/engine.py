"""vLLM-lite serving engine: token-budget continuous batching over a slotted
KV cache.

Default path — ONE jitted program (the unified mixed prefill/decode step):

  unified_fn(params, tokens(B, chunk), q_lens(B,), v_lens(B,),
             (cache, expert_counts), key)
      -> (tokens(B, n), n_emit(B,), last_logits(B, V), step_logits,
          (cache, expert_counts), bad(B,))
  (n = 1 + speculation k; step_logits = every row's (B, chunk, V) logits
   under ``debug_logits``, else None — the hot path runs the LM head only
   on the selected rows; bad[i] flags a non-finite sampled-logits row, the
   NaN/Inf quarantine signal; expert_counts is the device-side (E,) int32
   routed-slot accumulator that rides beside the KV state)

Every iteration each slot contributes ``q_lens[i] ∈ {0, 1, …, chunk}``
tokens against the fixed (B, chunk) buffer: a decoding slot contributes its
1 sampled token — or, when speculating, 1 + k rows whose draft tail is
greedy-verified in the same pass (``v_lens`` marks the verify slots) — a
prefilling slot contributes the next chunk of its pending tokens, an idle
slot contributes 0.  Admission is just bookkeeping
(the slot's pending buffer is ``prompt + tokens generated so far`` — the
recompute-on-resume suffix is what makes preemption exact — and the slot's
cache length is zeroed); no blocking prefill, so a long prompt never stalls
the decode slots.  Ragged tails are masked at every level (docs/serving.md,
docs/dispatch.md).

Robustness (docs/serving.md "Robustness & degradation"):

- every ``Request`` carries a lifecycle ``state``
  (QUEUED/RUNNING/DONE/CANCELLED/SHED/FAILED/PREEMPTED), a ``priority``
  and an optional ``deadline_s``;
- ``release``/``cancel`` free a slot mid-decode (deadline kills, user
  cancellation), ``preempt`` evicts a slot for recompute-on-resume (the
  vLLM recompute strategy — works on the dense per-slot cache today and
  carries over verbatim to paged KV);
- a NaN/Inf guard on the sampled-logits rows quarantines exactly the
  offending slot (state FAILED) instead of propagating;
- a ``FaultInjector`` (``repro.serving.faults``, wired through
  ``ServeSpec.faults``) deterministically injects latency spikes, NaN
  rows, admit failures and clock skew for chaos testing.

Tracing (docs/serving.md "Tracing"): each unified step is an
``engine.step`` profiler span whose children name its host phases (plan,
reap, pack, upload, launch, readback, commit), and ``step_stats`` keeps
the same boundaries as counters.  With no profiler running a span costs
about a microsecond.

Legacy path — the pre-unified two-program engine (bucket-padded blocking
prefill in ``admit`` + a separate decode program) survives ONLY for
families the unified step cannot serve (``unified_supported`` False:
recurrent state, hybrid ring buffers, enc-dec/stub frontends).

The engine is configured by ONE object: a
``repro.serving.api.ResolvedServeSpec`` (``Engine(cfg, params, spec)``)
carrying the analyzer-selected ShardingPlan, the ``KernelPolicy``, the MoE
``dispatch`` mode, the chunk/token-budget/slot envelope, the overload
policy and the fault plan.  The PR 5 per-knob kwargs shim
(``max_batch=``, ``chunk=``, ...) has been removed after its one-release
window — build a ``ServeSpec`` and resolve it (docs/api.md).
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import Counter
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.cost_model import cap_rows_for
from repro.kernels import ops as kops
from repro.models.model import forward, init_cache
from repro.serving.draft import make_draft
from repro.serving.faults import FaultInjector, InjectedFault
from repro.serving.kv_cache import insert_slot, make_kv_cache, with_lengths


class PromptTooLongError(ValueError):
    """Prompt (+ frontend tokens + generation budget) cannot fit the cache."""


class RequestState:
    """Request lifecycle.  Terminal states never transition again;
    PREEMPTED requests re-enter RUNNING when re-admitted (recompute)."""

    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    DONE = "DONE"
    CANCELLED = "CANCELLED"
    SHED = "SHED"
    FAILED = "FAILED"
    PREEMPTED = "PREEMPTED"

    TERMINAL = frozenset({DONE, CANCELLED, SHED, FAILED})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (s,) int32 token ids
    max_new_tokens: int = 32
    arrival: float = 0.0
    priority: int = 0                   # higher = more important
    deadline_s: Optional[float] = None  # seconds after arrival; None = none
    # filled by the engine / scheduler:
    state: str = RequestState.QUEUED
    error: str = ""
    n_preempted: int = 0
    out_tokens: list = dataclasses.field(default_factory=list)
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens

    @property
    def terminal(self) -> bool:
        return self.state in RequestState.TERMINAL

    @property
    def deadline(self) -> float:
        """Absolute deadline (same clock as ``arrival``); +inf if none."""
        if self.deadline_s is None:
            return float("inf")
        return self.arrival + self.deadline_s

    @property
    def ttft(self) -> float:
        return self.t_first_token - self.arrival

    @property
    def itl(self) -> float:
        n = len(self.out_tokens)
        if n <= 1:
            return 0.0
        return (self.t_done - self.t_first_token) / (n - 1)


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


MAX_BUCKET = 4096      # largest legacy prefill bucket — hard prompt cap


def unified_supported(cfg: ModelConfig) -> bool:
    """Whether the unified mixed step can serve this config.

    Attention-cached text families only: recurrent (rwkv) and ring-buffer
    (hybrid local-attn) state advances by every scanned row so a ragged
    tail cannot be masked, and stub-frontend models (vision/audio) need
    per-request embeds injected at prefill — both stay on the legacy path.
    """
    return cfg.family in ("dense", "moe", "vlm") and not cfg.frontend \
        and not cfg.mrope


class Engine:
    def __init__(self, cfg: ModelConfig, params, spec=None, *,
                 embeds_fn: Optional[Callable] = None, dtype=None):
        # ``spec`` (a serving.api.ResolvedServeSpec) is THE configuration
        # surface: strategy/plan, kernels, dispatch, chunk, token budget,
        # the slot envelope, overload policy and fault plan all ride on it.
        # ``dtype`` (the KV state's) defaults to the weights' own dtype.
        if spec is None:
            raise TypeError(
                "Engine needs a resolved spec: Engine(cfg, params, "
                "ServeSpec(...).resolve()) — the per-knob kwargs shim was "
                "removed after its one-release window (docs/api.md)")
        if dtype is None:
            dtype = next(x.dtype for x in jax.tree.leaves(params)
                         if jnp.issubdtype(x.dtype, jnp.floating))
        self.spec = spec
        self.cfg, self.params, self.plan = cfg, params, spec.plan
        self.max_batch, self.max_len = spec.max_batch, spec.max_len
        self.temperature = spec.temperature
        self.key = jax.random.PRNGKey(spec.seed)
        self.embeds_fn = embeds_fn    # vlm/audio stub-frontend provider
        self.chunk = max(1, min(int(spec.chunk), spec.max_len))
        # debug/oracle mode: keep every row's logits (B, chunk, V) per step
        # in ``step_logits``; the hot path applies the LM head only to each
        # slot's last valid row (forward last_only)
        self.debug_logits = bool(spec.debug_logits)

        # expert-load observability (MoE only, unified path): per-expert
        # routed-slot counts summed over layers/steps, accumulated on the
        # device by the unified step and read only when asked
        # (``expert_counts``), plus the EP-exchange byte ledger — moved
        # under the resolved count-bounded extent vs the monolithic worst
        # case (``ep_load_stats``)
        n_e = max(cfg.n_experts, 1)
        self._ecnt = jnp.zeros((n_e,), jnp.int32)
        self._ecnt_host = np.zeros((n_e,), np.int64)
        # the int32 accumulator is folded into the host's int64 copy before
        # it can overflow: a step routes at most every buffer row to top_k
        # experts in every layer
        per_step = self.max_batch * self.chunk * max(cfg.top_k, 1) \
            * cfg.n_layers
        self._ecnt_fold_every = max(1, (2 ** 31 - 1) // per_step)
        self._ecnt_steps = 0
        self.a2a_bytes_moved = 0
        self.a2a_bytes_worst = 0
        # step counters at the span boundaries (``step_stats``): rows fed
        # and computed, the host's time per step outside the read-back,
        # and the read-back waits
        self._rows_fed = self._rows_buffer = 0
        self._n_steps = 0
        self._host_s = 0.0
        self._readback_s = self._readback_max_s = 0.0
        # kernel call sites in the traced step program (``_step_forward``)
        self._step_sites: Counter = Counter()

        # deterministic chaos harness (ServeSpec.faults); empty = inert
        self.faults = FaultInjector(getattr(spec, "faults", ()),
                                    seed=spec.seed)
        # robustness event counters, merged into ServeMetrics by the
        # scheduler: fault / preempt / cancel / deadline_miss
        self.events: Counter = Counter()

        # the blocking-prefill path survives ONLY as the automatic fallback
        # for families the unified step cannot serve (ssm/hybrid/frontend)
        self.legacy = not unified_supported(cfg)

        # ONE KVCache owns the device KV state (docs/kv_cache.md): the
        # resolved ``spec.kv`` picks dense per-slot buffers or the paged
        # pool + block tables + prefix index.  The legacy path's insert_slot
        # prefill only understands dense buffers, so it pins that backend.
        kvcfg = getattr(spec, "kv", None)
        if self.legacy and kvcfg is not None and kvcfg.backend != "dense":
            kvcfg = None
        self.kv = make_kv_cache(cfg, kvcfg, self.max_batch, self.max_len,
                                dtype)

        # speculative decoding (resolved ``spec.speculation`` or None): a
        # speculating slot contributes 1 + k rows (last committed token +
        # k draft tokens) to the SAME unified step, greedy verify accepts
        # the longest matching draft prefix plus one bonus token, and the
        # rejected tail rolls back (device length in the jitted step,
        # paged pages via kv.rollback).  Greedy-only and unified-only —
        # the resolver enforces both, this re-checks defensively.
        sc = getattr(spec, "speculation", None)
        self.spec_cfg = self.draft = None
        self.spec_k = 0
        if sc is not None and not self.legacy and self.temperature == 0:
            k = min(int(sc.k), self.chunk - 1)
            if k >= 1:
                self.spec_cfg = sc if sc.k == k \
                    else dataclasses.replace(sc, k=k)
                self.spec_k = k
                self.draft = make_draft(self.spec_cfg, cfg, params,
                                        plan=self.plan,
                                        max_batch=self.max_batch,
                                        max_len=self.max_len, dtype=dtype)
        self.n_logits = self.spec_k + 1   # logit rows per slot per step
        # per-slot draft tokens planned for the NEXT unified step
        self._drafts: list[Optional[np.ndarray]] = [None] * self.max_batch
        # acceptance accounting: slot-steps that drafted, tokens proposed/
        # accepted, and the EMA that gates drafting (optimistic start so
        # the first steps always probe)
        self.spec_steps = self.spec_drafted = self.spec_accepted = 0
        self.accept_ema = 1.0

        self.slots: list[Optional[Request]] = [None] * self.max_batch
        self.cur_tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
        # unified-step slot bookkeeping (host side, mirrors device lengths)
        self._pending = [None] * self.max_batch   # tokens to prefill
        self._prompt_pos = [0] * self.max_batch   # pending tokens written
        self._last_tok = [0] * self.max_batch     # last sampled token
        self._admit_seq = [0] * self.max_batch    # admission (prefill FIFO)
        self._seq = 0
        self._step_idx = 0                # engine step counter (fault keys)
        self.last_step_tokens = 0         # tokens processed by the last
        #                                   step — the watchdog's progress
        #                                   signal (0 = the step was a no-op)
        self.last_logits = None                # (B, V) of the last step
        self.step_logits = None                # (B, chunk, V), debug_logits

        self._prefill_cache = {}
        self._decode = jax.jit(self._decode_impl)
        self._unified = jax.jit(self._unified_impl)
        self.dtype = dtype

    # -- KV state --------------------------------------------------------
    @property
    def cache(self):
        """The live device KV pytree (owned by ``self.kv``)."""
        return self.kv.cache

    @cache.setter
    def cache(self, value):
        self.kv.cache = value

    # -- validation ------------------------------------------------------
    def validate(self, req: Request) -> None:
        """Reject a request whose prompt + generation can never fit.

        The legacy path used to let an over-length prompt overflow the
        bucket buffer / cache writes silently (positions past max_len were
        clamp-scattered onto the last rows) — now both paths refuse it
        up front with a clear error.
        """
        s = len(req.prompt) + self._front_len()
        need = s + max(0, req.max_new_tokens - 1)
        if self.legacy:
            # the blocking prefill writes a whole bucket-padded buffer into
            # the cache, so the BUCKET must fit, not just the prompt
            need = max(need, _bucket(len(req.prompt)) + self._front_len())
        if len(req.prompt) > MAX_BUCKET or need > self.max_len:
            raise PromptTooLongError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"(+{self._front_len()} frontend, +{req.max_new_tokens} new) "
                f"needs {need} cache positions but max_len={self.max_len} "
                f"(prompt cap {MAX_BUCKET}) — raise max_len, shorten the "
                "prompt, or lower max_new_tokens")
        if self.kv.pool_tokens is not None and need > self.kv.pool_tokens:
            raise PromptTooLongError(
                f"request {req.rid}: needs {need} cache positions but the "
                f"paged KV pool holds {self.kv.pool_tokens} tokens — raise "
                "ServeSpec.kv pool_pages (or leave kv='auto')")

    # -- jitted programs -------------------------------------------------
    def _step_forward(self, params, **kw):
        """``forward`` inside the step program; keeps the kernel call sites
        its trace counted (``kernels.ops.counters``) for ``step_stats``."""
        before = kops.counters.copy()
        out = forward(params, self.cfg, self.plan, **kw)
        self._step_sites = kops.counters - before
        return out

    def _unified_impl(self, params, tokens, q_lens, v_lens, state, key):
        """THE serving program: one mixed token-budget iteration.

        tokens (B, chunk) int32, q_lens (B,) int32.  Slot i's valid rows are
        tokens[i, :q_lens[i]] — a prefill chunk, a single decode token, or
        a 1 + k speculative verify block — at cache offset length[i]; rows
        past q_lens[i] are inert.  ``v_lens[i]`` marks decode-phase slots
        (v = q: the rows are [last committed token, k drafts]); 0 means
        prefill/idle.  ``bad[i]`` flags a non-finite sampled-logits row on
        a scheduled slot — the NaN/Inf quarantine signal.  ``state`` is the
        pair (KV cache, expert_counts): the (E,) int32 routed-slot
        accumulator rides beside the cache, in and out, so the counts stay
        on the device until ``Engine.expert_counts`` asks for them.

        Returns (toks (B, n), n_emit (B,), last (B, V), step_logits,
        (cache, expert_counts), bad (B,)) with n = 1 + spec_k.  Slot i's
        committed tokens are ``toks[i, :n_emit[i]]``: greedy verify accepts
        draft j+1 iff it equals the argmax of row j's logits (row j is the
        prediction after consuming rows 0..j), so the accepted drafts ARE
        the greedy rows and the first rejected position contributes its
        greedy token as the bonus — bit-exact vs non-speculative greedy by
        construction.  The rejected tail's cache rows are subtracted from
        the returned per-slot length (stale rows beyond it are masked like
        any ragged tail).  With spec_k == 0 this is exactly the
        non-speculative program (n_emit is 1 on every scheduled slot and
        temperature sampling applies).
        """
        cache, counts = state
        n = self.n_logits
        if n == 1:                        # non-speculative: the PR 8 graph
            out = self._step_forward(params, tokens=tokens,
                                     cache=cache, q_lens=q_lens,
                                     last_only=not self.debug_logits,
                                     expert_stats=self.cfg.is_moe)
            if self.debug_logits:
                last = jnp.take_along_axis(
                    out.logits, jnp.maximum(q_lens - 1, 0)[:, None, None],
                    axis=1)[:, 0]                           # (B, V)
                step_logits = out.logits
            else:
                last = out.logits[:, 0]
                step_logits = None
            bad = (q_lens > 0) & ~jnp.isfinite(last).all(axis=-1)
            if self.temperature > 0:
                nxt = jax.random.categorical(key, last / self.temperature, -1)
            else:
                nxt = jnp.argmax(last, -1)
            emit = jnp.where(q_lens > 0, 1, 0).astype(jnp.int32)
            return (nxt.astype(jnp.int32)[:, None], emit, last, step_logits,
                    (out.cache, self._add_counts(counts, out)), bad)

        # speculative verify: score one logit row per draft position.  Row
        # j's logits are the model's prediction AFTER consuming rows 0..j
        # (causal flash_chunk masks per row, so the extra rows never touch
        # earlier rows' softmax).  Non-verify slots (prefill: v = 0) pin
        # every row to their last valid row, so toks[:, 0] is the plain
        # sampled token.
        last_row = jnp.maximum(q_lens - 1, 0)               # (B,)
        j = jnp.arange(n)
        rows = jnp.where((v_lens > 0)[:, None],
                         jnp.minimum(j[None, :], last_row[:, None]),
                         last_row[:, None])                 # (B, n)
        out = self._step_forward(params, tokens=tokens,
                                 cache=cache, q_lens=q_lens,
                                 last_only=not self.debug_logits,
                                 logit_rows=None if self.debug_logits
                                 else rows,
                                 expert_stats=self.cfg.is_moe)
        if self.debug_logits:
            sel = jnp.take_along_axis(out.logits, rows[:, :, None], axis=1)
            step_logits = out.logits
        else:
            sel = out.logits                                # (B, n, V)
            step_logits = None
        bad = (q_lens > 0) & ~jnp.isfinite(sel).all(axis=(1, 2))
        greedy = jnp.argmax(sel, -1).astype(jnp.int32)      # (B, n)
        s = jnp.maximum(v_lens - 1, 0)                      # drafted count
        drafts = tokens[:, 1:n]                             # (B, n-1)
        ok = (drafts == greedy[:, :n - 1]) \
            & (jnp.arange(n - 1)[None, :] < s[:, None])
        # accepted = longest matching prefix; +1 bonus token from the
        # first non-accepted row's own greedy prediction
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        emit = jnp.where(q_lens > 0,
                         jnp.where(v_lens > 0, acc + 1, 1),
                         0).astype(jnp.int32)
        rollback = jnp.where(v_lens > 0, s - acc, 0).astype(jnp.int32)
        cache2 = {**out.cache, "length": out.cache["length"] - rollback}
        return (greedy, emit, sel[:, 0], step_logits,
                (cache2, self._add_counts(counts, out)), bad)

    @staticmethod
    def _add_counts(counts, out):
        return counts if out.expert_counts is None \
            else counts + out.expert_counts

    def _prefill_impl(self, params, tokens, real_len):
        cache = init_cache(self.cfg, 1, self.max_len, self.dtype)
        kw = {}
        if self.embeds_fn is not None:
            kw = self.embeds_fn(1)
        out = forward(params, self.cfg, self.plan, tokens=tokens,
                      cache=cache, **kw)
        # bucketed prompt: logits of the last REAL token
        last = out.logits[:, real_len - 1 + self._front_len() ]
        cache = with_lengths(out.cache,
                             jnp.full((1,), real_len + self._front_len(),
                                      jnp.int32))
        return last, cache

    def _front_len(self) -> int:
        if self.cfg.frontend == "vision_stub":
            return self.cfg.n_frontend_tokens
        return 0

    def _decode_impl(self, params, tokens, cache, active, key):
        out = forward(params, self.cfg, self.plan, tokens=tokens, cache=cache)
        logits = out.logits[:, 0]
        if self.temperature > 0:
            nxt = jax.random.categorical(key, logits / self.temperature, -1)
        else:
            nxt = jnp.argmax(logits, -1)
        # only advance lengths of active slots
        new_len = jnp.where(active, out.cache["length"], cache["length"])
        return nxt.astype(jnp.int32), with_lengths(out.cache, new_len)

    # -- slot management -------------------------------------------------
    def free_slots(self) -> list:
        return [i for i, r in enumerate(self.slots) if r is None]

    def slot_of(self, rid: int) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                return i
        return None

    def admit(self, req: Request) -> bool:
        """Admit into a free slot.  Unified path: pure bookkeeping — the
        slot's pending buffer becomes ``prompt + out_tokens`` (the
        recompute-on-resume stream; plain prompt for a fresh request) and
        the slot's cache length is zeroed.  Legacy path: the old blocking
        bucket-padded prefill.  Raises ``InjectedFault`` when an "admit"
        fault targets this request (the scheduler sheds it)."""
        with TraceAnnotation("serve.admit", rid=req.rid):
            return self._admit(req)

    def _admit(self, req: Request) -> bool:
        self.validate(req)
        fault = self.faults.admit_blocked(self._step_idx, req.rid) \
            if self.faults else None
        if fault is not None:
            raise InjectedFault(
                f"request {req.rid}: injected admission failure "
                f"({fault.describe()})")
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        if self.legacy:
            return self._admit_legacy(req, slot)
        self.slots[slot] = req
        # resume replays generated-so-far tokens as a prompt suffix: the
        # prefill of prompt+out reproduces the evicted KV exactly, and the
        # next sampled token continues the sequence bit-for-bit (greedy)
        self._pending[slot] = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out_tokens, np.int32)]) \
            if req.out_tokens else np.asarray(req.prompt, np.int32)
        # paged KV: ``begin`` matches the pending stream against the prefix
        # index and returns how many leading tokens are already cached —
        # prefill starts past them (a warm restart / shared system prompt
        # skips straight to its unique tail).  Dense: always 0.
        self._prompt_pos[slot] = self.kv.begin(slot, self._pending[slot])
        self._last_tok[slot] = 0
        if self.draft is not None:
            self.draft.begin(slot, self._pending[slot])
        self._admit_seq[slot] = self._seq
        self._seq += 1
        if req.t_admitted == 0.0:
            req.t_admitted = time.perf_counter()
        req.state = RequestState.RUNNING
        return True

    def _admit_legacy(self, req: Request, slot: int) -> bool:
        s = len(req.prompt)
        bucket = _bucket(s)
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = jax.jit(
                self._prefill_impl, static_argnames=())
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :s] = req.prompt
        last_logits, cache1 = self._prefill_cache[bucket](
            self.params, jnp.asarray(toks), s)
        first = int(jnp.argmax(last_logits[0]))
        self.cache = insert_slot(self.cache, cache1, slot)
        self.cur_tokens = self.cur_tokens.at[slot, 0].set(first)
        req.out_tokens.append(first)
        req.t_admitted = req.t_first_token = time.perf_counter()
        req.state = RequestState.RUNNING
        self.slots[slot] = req
        return True

    def release(self, slot: int, state: str, error: str = "",
                reason: str = "") -> Optional[Request]:
        """Free a slot mid-flight: deadline kill, cancel, fault quarantine.

        Zeroes the slot's cache length (rows become masked stale data for
        the next occupant) and stamps the request's terminal/transition
        state.  ``reason`` increments the engine's event counter.
        """
        req = self.slots[slot]
        if req is None:
            return None
        self.slots[slot] = None
        self._pending[slot] = None
        self._drafts[slot] = None
        if self.draft is not None:
            self.draft.release(slot)
        # free the slot's KV; a quarantined slot's pages may hold the very
        # NaNs we are quarantining, so they never enter the prefix index
        self.kv.free(slot, keep_prefix=state != RequestState.FAILED)
        req.state = state
        if error:
            req.error = error
        if state in RequestState.TERMINAL:
            req.t_done = time.perf_counter()
        if reason:
            self.events[reason] += 1
        return req

    def cancel(self, rid: int) -> Optional[Request]:
        """Cancel a RUNNING request; frees its slot immediately."""
        slot = self.slot_of(rid)
        if slot is None:
            return None
        return self.release(slot, RequestState.CANCELLED, error="cancelled",
                            reason="cancel")

    def preempt(self, slot: int) -> Optional[Request]:
        """Evict a slot for a higher-priority request (recompute-on-resume).

        The dense per-slot cache is simply abandoned (length zeroed); on
        re-admission the pending buffer ``prompt + out_tokens`` recomputes
        it, so the resumed request's final output matches its
        uninterrupted run exactly.  Paged KV makes the eviction
        cache-preserving: ``kv.free`` parks the victim's computed full
        pages in the prefix index, so the resume's ``begin`` re-matches
        them and only the uncached tail is recomputed.
        """
        req = self.release(slot, RequestState.PREEMPTED, reason="preempt")
        if req is not None:
            req.n_preempted += 1
        return req

    def victim_slot(self, below_priority: int) -> Optional[int]:
        """Lowest-priority occupied slot strictly below ``below_priority``
        — the preemption victim (youngest admission breaks ties, so the
        request with the most sunk work keeps its slot)."""
        best = None
        for i, r in enumerate(self.slots):
            if r is None or r.priority >= below_priority:
                continue
            key = (r.priority, -self._admit_seq[i])
            if best is None or key < best[0]:
                best = (key, i)
        return None if best is None else best[1]

    # -- token-budget planning (Sarathi-style, decode-first) -------------
    def plan_q_lens(self, token_budget: Optional[int] = None) -> np.ndarray:
        """Per-slot token counts for the next unified step.

        Decode slots always get their 1 token (they are never starved by
        prefill work); the remaining budget — default ``max_batch * chunk``
        — is filled with prefill chunks in admission (FIFO) order, each
        capped at ``chunk``.

        Every grant passes through ``kv.reserve`` so the paged backend can
        shrink it to what the pool can hold (dense grants everything) — a
        slot the pool cannot extend simply sits out the step; ``step``
        breaks a full deadlock by preempting.
        """
        budget = int(token_budget) if token_budget else \
            self.max_batch * self.chunk
        q = np.zeros((self.max_batch,), np.int32)
        prefilling, decoding = [], []
        for i, r in enumerate(self.slots):
            if r is None or r.terminal:
                continue
            if self._prompt_pos[i] < len(self._pending[i]):
                prefilling.append(i)
            elif not r.done:
                q[i] = self.kv.reserve(i, 1)
                decoding.append(i)
        budget -= int(q.sum())
        if self.draft is not None:
            # draft rows are decode-side work: price them BEFORE prefill
            # (a speculating slot costs 1 + k budget rows) — the auto
            # budget max_batch*(1+k) + chunk keeps the prefill chunk
            # funded even when every slot speculates
            budget = self._plan_drafts(decoding, q, budget)
        for i in sorted(prefilling, key=lambda j: self._admit_seq[j]):
            if budget <= 0:
                break
            n = min(self.chunk, len(self._pending[i])
                    - self._prompt_pos[i], budget)
            n = self.kv.reserve(i, n)
            q[i] = n
            budget -= n
        return q

    def _plan_drafts(self, decoding: list, q: np.ndarray,
                     budget: int) -> int:
        """Extend decode slots with draft rows, priced against the budget.

        Each speculating slot's grant grows from 1 to 1 + k rows where
        k <= spec_k is trimmed by the request's remaining generation room,
        the budget, and what the KV pool can actually reserve.  Drafting
        pauses when the acceptance EMA drops under the configured gate,
        re-probing every ``probe_every`` steps.  Returns remaining budget.
        """
        self._drafts = [None] * self.max_batch
        sc = self.spec_cfg
        if not decoding or budget <= 0:
            return budget
        if self.accept_ema < sc.min_accept \
                and self._step_idx % sc.probe_every != 0:
            return budget                  # gated off; periodic re-probe
        want, ctx = {}, {}
        for i in decoding:
            if q[i] < 1:
                continue                   # pool could not even extend by 1
            r = self.slots[i]
            # full acceptance commits k + 1 tokens; keep <= max_new_tokens
            room = r.max_new_tokens - len(r.out_tokens) - 1
            k = min(self.spec_k, room, budget)
            if k < 1:
                continue
            ctx[i] = np.concatenate([np.asarray(r.prompt, np.int64),
                                     np.asarray(r.out_tokens, np.int64)])
            want[i] = k
        if not want:
            return budget
        props = self.draft.propose(ctx, want)
        for i in sorted(props, key=lambda jj: self._admit_seq[jj]):
            if budget <= 0:
                break
            d = np.asarray(props[i], np.int64)[:want[i]][:budget]
            if d.size == 0:
                continue
            # the decode row already reserved 1; extend to 1 + k rows
            # (paged exhaustion grants fewer — trim the draft to fit)
            grant = self.kv.reserve(i, 1 + int(d.size))
            d = d[:max(0, grant - 1)]
            if d.size == 0:
                continue
            self._drafts[i] = d
            q[i] += int(d.size)
            budget -= int(d.size)
        return budget

    # -- stepping --------------------------------------------------------
    def step(self, token_budget: Optional[int] = None) -> list:
        """One engine iteration.  Returns retired requests (state DONE, or
        FAILED for NaN-quarantined slots).

        Unified: one mixed token-budget step over all slots, traced as an
        ``engine.step`` span with the plan's row counts as its args.
        Legacy: one decode step for all active (fully prefilled) slots."""
        if self.legacy:
            return self._step_legacy()
        t0, rb0 = time.perf_counter(), self._readback_s
        with TraceAnnotation("engine.step", step=self._step_idx,
                             buffer=self.max_batch * self.chunk) as span:
            with TraceAnnotation("engine.plan"):
                q, preempted = self._plan_step(token_budget)
            pre = sum(int(q[i]) for i, p in enumerate(self._pending)
                      if p is not None and self._prompt_pos[i] < len(p))
            span.set_metadata(rows=int(q.sum()), prefill_rows=pre,
                              decode_rows=int(q.sum()) - pre,
                              slots=self.n_active)
            retired = preempted + self.unified_step(q)
        self._n_steps += 1
        self._host_s += (time.perf_counter() - t0
                         - (self._readback_s - rb0))
        return retired

    def _plan_step(self, token_budget: Optional[int]) -> tuple:
        """The step's per-slot plan, and the slots preempted to get one."""
        q = self.plan_q_lens(token_budget)
        preempted = []
        # paged-pool deadlock breaker: live slots exist but the pool could
        # not extend ANY of them — free a victim's pages (cache-preserving:
        # its prompt pages drop into the prefix index) and replan.  Never
        # fires on dense (reserve always grants) and never preempts the
        # last live slot (validate bounds a lone request by the pool).
        while (not q.any()
               and any(r is not None and not r.terminal and not r.done
                       for r in self.slots)
               and self.n_active > 1):
            victim = self.victim_slot(1 << 30)
            if victim is None:
                break
            preempted.append(self.preempt(victim))
            q = self.plan_q_lens(token_budget)
        return q, preempted

    def _reap(self) -> list:
        """Sweep slots already retired (terminal state set by cancel/
        release) or trivially complete (max_new_tokens satisfied — e.g. a
        zero-token request, which previously pinned its slot and busy-spun
        the scheduler forever)."""
        retired = []
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            if r.terminal:
                self.slots[i] = None
                self._pending[i] = None
                self.kv.free(i)
                retired.append(r)
            elif r.done:
                r.state = RequestState.DONE
                r.t_done = r.t_done or time.perf_counter()
                self.slots[i] = None
                self._pending[i] = None
                self.kv.free(i)
                retired.append(r)
            else:
                continue
            self._drafts[i] = None
            if self.draft is not None:
                self.draft.release(i)
        return retired

    def unified_step(self, q_lens) -> list:
        """Run the jitted unified step with an explicit per-slot plan."""
        step_idx = self._step_idx
        self._step_idx += 1
        with TraceAnnotation("engine.reap"):
            retired = self._reap()
        q_lens = np.asarray(q_lens, np.int32)
        self.last_step_tokens = int(q_lens.sum())
        if self.faults:
            spike = self.faults.step_latency_s(step_idx)
            if spike > 0:
                time.sleep(spike)            # injected straggler
        if not q_lens.any():
            return retired
        with TraceAnnotation("engine.pack"):
            toks, v_lens = self._pack(q_lens)
        with TraceAnnotation("engine.upload"):
            self.key, sub = jax.random.split(self.key)
            self.kv.flush()          # push dirty block tables to device
            args = (jnp.asarray(toks), jnp.asarray(q_lens),
                    jnp.asarray(v_lens))
        with TraceAnnotation("engine.launch", step=step_idx):
            (nxt, emit, self.last_logits, self.step_logits,
             (self.cache, self._ecnt), bad) = self._unified(
                self.params, *args, (self.cache, self._ecnt), sub)
        # the step's only host reads, (B, n) tokens and two (B,) vectors,
        # for request bookkeeping + the next step's token buffer (which
        # must merge host-side prompt chunks anyway); the expert counts
        # stay on the device
        t0 = time.perf_counter()
        with TraceAnnotation("engine.readback", step=step_idx):
            nxt_host = np.asarray(nxt)
            emit_host = np.asarray(emit)
            bad_host = np.array(bad)   # copy: fault injection writes into it
        wait = time.perf_counter() - t0
        self._readback_s += wait
        self._readback_max_s = max(self._readback_max_s, wait)
        self._rows_fed += self.last_step_tokens
        self._rows_buffer += toks.size
        with TraceAnnotation("engine.commit"):
            self._commit(step_idx, q_lens, v_lens, nxt_host, emit_host,
                         bad_host, retired)
        return retired

    def _pack(self, q_lens: np.ndarray) -> tuple:
        """The step's (B, chunk) token buffer and (B,) verify lengths."""
        toks = np.zeros((self.max_batch, self.chunk), np.int32)
        v_lens = np.zeros((self.max_batch,), np.int32)
        for i, r in enumerate(self.slots):
            n = int(q_lens[i])
            if r is None or n == 0:
                continue
            pos = self._prompt_pos[i]
            if pos < len(self._pending[i]):
                toks[i, :n] = self._pending[i][pos:pos + n]
            else:
                toks[i, 0] = self._last_tok[i]
                d = self._drafts[i]
                if d is not None and n == 1 + len(d):
                    toks[i, 1:n] = d          # verify rows: [t0, d1..dk]
                    v_lens[i] = n
                else:
                    v_lens[i] = min(n, 1)     # plain decode (stale/absent
                    #                           drafts never reach the step)
        return toks, v_lens

    def _commit(self, step_idx: int, q_lens, v_lens, nxt_host, emit_host,
                bad_host, retired: list) -> None:
        """Per-slot bookkeeping of a finished step: KV length mirror, the
        a2a ledger, committed tokens, quarantine, retirement."""
        if self.cfg.is_moe:
            self._account_a2a(int(q_lens.sum()))
            self._ecnt_steps += 1
            if self._ecnt_steps >= self._ecnt_fold_every:
                self._fold_counts()
        self.kv.advance(q_lens)  # host length mirror follows the device
        if self.faults:
            live = {i: r.rid for i, r in enumerate(self.slots)
                    if r is not None and q_lens[i] > 0}
            for i in self.faults.nan_slots(step_idx, live):
                bad_host[i] = True           # injected NaN-logits row
                self.last_logits = self.last_logits.at[i].set(jnp.nan)
        now = time.perf_counter()
        for i, r in enumerate(self.slots):
            n = int(q_lens[i])
            if r is None or n == 0:
                continue
            s_i = max(int(v_lens[i]) - 1, 0)     # draft rows this slot fed
            if bad_host[i]:
                # quarantine exactly this slot: non-finite logits never
                # produce a token, never touch a neighbour
                retired.append(self.release(
                    i, RequestState.FAILED, error="non-finite logits",
                    reason="fault"))
                continue
            pos = self._prompt_pos[i]
            if pos < len(self._pending[i]):            # prefill chunk
                self._prompt_pos[i] = pos + n
                if self._prompt_pos[i] < len(self._pending[i]):
                    continue                           # still prefilling
                if r.t_first_token == 0.0:
                    r.t_first_token = now              # prompt done: TTFT
            if r.done:                                 # zero-token budget:
                continue                               # reaped next sweep
            m = int(emit_host[i])                      # accepted + bonus
            if s_i:
                # release the rejected tail's pages (the device length is
                # already rolled back inside the jitted step); counters +
                # the acceptance EMA that gates the next plan's drafting
                a_i = m - 1
                self.kv.rollback(i, s_i - a_i)
                self.spec_steps += 1
                self.spec_drafted += s_i
                self.spec_accepted += a_i
                al = self.spec_cfg.ema_alpha
                self.accept_ema = ((1 - al) * self.accept_ema
                                   + al * (a_i / s_i))
            m = min(m, r.max_new_tokens - len(r.out_tokens))   # defensive
            if m <= 0:
                continue
            committed = [int(t) for t in nxt_host[i, :m]]
            r.out_tokens.extend(committed)
            self._last_tok[i] = committed[-1]
            if self.draft is not None:
                self.draft.observe(i, np.asarray(committed, np.int64))
            r.t_done = now
            if r.done:
                r.state = RequestState.DONE
                retired.append(r)
                self.slots[i] = None
                self._pending[i] = None
                self.kv.free(i)
                if self.draft is not None:
                    self.draft.release(i)
        self._drafts = [None] * self.max_batch    # drafts are one-shot

    def step_stats(self) -> dict:
        """Step counters for ServeMetrics: the share of computed rows
        (``max_batch * chunk`` per step) that carried a token, the host's
        time per ``step`` outside the read-back, and the longest read-back
        wait, counted at the ``engine.*`` span boundaries; and, from the
        trace of the step program, the share of its ``grouped_gemm`` call
        sites that read the layer-stacked expert weights in place and the
        count of sites whose wrapper padded the weights."""
        sites = self._step_sites
        return {
            "grouped_gemm_stacked_share": (
                sites["grouped_gemm_stacked"] / sites["grouped_gemm"]
                if sites["grouped_gemm"] else 0.0),
            "grouped_gemm_w_pad": sites["grouped_gemm_w_pad"],
            "fed_row_share": (self._rows_fed / self._rows_buffer
                              if self._rows_buffer else 0.0),
            "host_ms_per_step": (1e3 * self._host_s / self._n_steps
                                 if self._n_steps else 0.0),
            "readback_ms_max": 1e3 * self._readback_max_s,
        }

    def spec_stats(self) -> dict:
        """Speculation counters for ServeMetrics / bench meta."""
        steps, drafted = self.spec_steps, self.spec_drafted
        return {
            "n_spec_steps": int(steps),
            "n_spec_drafted": int(drafted),
            "n_spec_accepted": int(self.spec_accepted),
            "spec_accept_rate":
                float(self.spec_accepted / drafted) if drafted else 0.0,
            "spec_tokens_per_step":
                float((self.spec_accepted + steps) / steps) if steps else 0.0,
        }

    # -- expert-load / EP-exchange observability -------------------------
    @property
    def expert_counts(self) -> np.ndarray:
        """(E,) int64 routed-slot counts per expert, summed over layers and
        steps.  One device read, here: the unified step accumulates the
        counts on the device."""
        self._fold_counts()
        return self._ecnt_host.copy()

    def _fold_counts(self) -> None:
        """Move the device accumulator into the host's int64 counts."""
        self._ecnt_host += np.asarray(self._ecnt, np.int64)
        self._ecnt = jnp.zeros_like(self._ecnt)
        self._ecnt_steps = 0

    def _account_a2a(self, step_tokens: int) -> None:
        """Price the step's EP exchange into the byte ledger.

        The engine itself runs the model on this host (NULL_PLAN when
        single-device), so the ledger prices what the RESOLVED deployment
        would move: ``moved`` under the count-bounded micro-chunked extent
        the spec resolved to, ``worst`` under the monolithic worst-case
        buffers (every routed row replicated to every EP rank).  Both are
        dispatch + combine over every MoE layer.
        """
        cfg, spec = self.cfg, self.spec
        ep = int(getattr(spec, "moe_ep", 1) or 1)
        if not cfg.is_moe or step_tokens <= 0 or ep <= 1:
            return
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        n_rows = step_tokens * cfg.top_k     # routed slots per MoE layer
        ovl = getattr(spec, "ep_overlap", None)
        if ovl is None or getattr(ovl, "chunks", 1) <= 1:
            rows_moved = ep * n_rows         # monolithic worst-case extent
        else:
            chunks = max(1, math.gcd(int(ovl.chunks), step_tokens))
            n_chunk_rows = (step_tokens // chunks) * cfg.top_k
            cap = cap_rows_for(n_chunk_rows, ep, ovl)
            rows_moved = ep * chunks * cap   # count-bounded extent
        # fused RS-A2A-AG ships the TP shard (h / moe_tp); the unfused
        # ladder moves full-width rows
        moe_tp = int(getattr(spec, "moe_tp", 1) or 1)
        width = cfg.d_model
        if getattr(spec.plan, "comm_algo", "") == "fused":
            width = cfg.d_model // max(moe_tp, 1)
        elem = jnp.dtype(self.dtype).itemsize
        per_row = width * elem * 2 * n_moe_layers      # dispatch + combine
        self.a2a_bytes_moved += rows_moved * per_row
        self.a2a_bytes_worst += ep * n_rows * per_row

    def ep_load_stats(self) -> dict:
        """Expert-load skew bucketed by resolved EP rank + the A2A ledger.

        Returns ``ep_rank_max_tokens`` / ``ep_rank_mean_tokens`` (routed
        slots landing on the hottest / average EP rank, summed over layers
        and steps — their ratio is the skew the count-bounded buffers must
        absorb) and the ``a2a_bytes_moved`` / ``a2a_bytes_worst`` ledger.
        """
        counts = self.expert_counts
        ep = int(getattr(self.spec, "moe_ep", 1) or 1)
        if not self.cfg.is_moe or counts.sum() == 0:
            return {"ep_rank_max_tokens": 0, "ep_rank_mean_tokens": 0.0,
                    "a2a_bytes_moved": int(self.a2a_bytes_moved),
                    "a2a_bytes_worst": int(self.a2a_bytes_worst)}
        if ep <= 1 or counts.shape[0] % ep:
            ep = 1                      # degenerate bucketing: one rank
        per_rank = counts.reshape(ep, counts.shape[0] // ep).sum(axis=1)
        return {"ep_rank_max_tokens": int(per_rank.max()),
                "ep_rank_mean_tokens": float(per_rank.mean()),
                "a2a_bytes_moved": int(self.a2a_bytes_moved),
                "a2a_bytes_worst": int(self.a2a_bytes_worst)}

    def _step_legacy(self) -> list:
        step_idx = self._step_idx
        self._step_idx += 1
        if self.faults:
            spike = self.faults.step_latency_s(step_idx)
            if spike > 0:
                time.sleep(spike)
        # reap requests already complete or externally released: the
        # blocking prefill emits the first token inside admit, so a
        # max_new_tokens==1 request is done before its first decode step —
        # without this sweep it would pin its slot forever (and the append
        # loop below would push a token past its budget)
        finished = []
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            if r.terminal:
                self.slots[i] = None
                finished.append(r)
            elif r.done:
                r.state = RequestState.DONE
                r.t_done = r.t_done or time.perf_counter()
                self.slots[i] = None
                finished.append(r)
        active = jnp.asarray([r is not None for r in self.slots])
        self.last_step_tokens = int(sum(r is not None for r in self.slots))
        if not bool(active.any()):
            return finished
        self.key, sub = jax.random.split(self.key)
        nxt, self.cache = self._decode(self.params, self.cur_tokens,
                                       self.cache, active, sub)
        # next step's inputs stay on device; the host reads the tokens once,
        # purely for request bookkeeping (no device->host->device round trip)
        self.cur_tokens = nxt[:, None]
        now = time.perf_counter()
        nxt_host = np.asarray(nxt)
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            r.out_tokens.append(int(nxt_host[i]))
            r.t_done = now
            if r.done:
                r.state = RequestState.DONE
                finished.append(r)
                self.slots[i] = None
        return finished

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)


__all__ = ["Engine", "Request", "RequestState", "PromptTooLongError",
           "unified_supported", "MAX_BUCKET"]
