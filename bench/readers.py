"""Arithmetic that several per-layer metric readers share.  Each metric's
own reader (``metrics/<name>.py``) picks what it reads; every function here
returns None where the run gives it nothing to read, never 0."""

from __future__ import annotations

from bench import costs
from bench import trace as tr


def idle_share(run):
    """Percent of the traced window with no operation on the device."""
    if run.trace is None or not run.trace.ops:
        return None
    w0, w1 = tr.window(run.trace)
    return 100.0 * (1.0 - tr.busy_s(run.trace) / (w1 - w0))


def step_ms(run):
    """Mean device time of one serving-step program, in ms."""
    steps = [] if run.trace is None else tr.program_steps(run.trace)
    if not steps:
        return None
    return 1e3 * sum(e.dur for e in steps) / len(steps)


def window_steps(run):
    return [s for s in run.rec.steps if run.t_open <= s.t1 <= run.t_close]


def step_mfu(run):
    """Model FLOPs of the real tokens the window's steps processed, over
    the window times the chip's peak, in percent."""
    steps = window_steps(run)
    if not steps or run.peaks is None:
        return None
    a = run.arch
    flops = 0.0
    for s in steps:
        for q, off in zip(s.q_lens.tolist(), s.offsets.tolist()):
            if q > 0:
                flops += q * costs.token_flops(a, off + (q + 1) / 2) \
                    + costs.head_flops(a)
    return 100.0 * flops / ((run.t_close - run.t_open)
                            * run.peaks.bf16_flops)


def grouped_gemm_roofline(run):
    """Least time of the window's grouped GEMM calls at their shapes (rows
    = every routed row of the step's token buffer), over their device
    time, in percent."""
    if run.trace is None or run.peaks is None:
        return None
    secs, n_ops = tr.kernel_s(run.trace, "grouped_gemm")
    n_steps = len(tr.program_steps(run.trace))
    if not n_ops or not n_steps:
        return None
    a, spec = run.arch, run.spec
    rows = spec.max_batch * spec.chunk * a.top_k
    e, d, f = a.n_experts, a.d_model, a.d_expert
    per_layer = [costs.grouped_gemm(rows, d, f, e),      # up
                 costs.grouped_gemm(rows, d, f, e),      # gate
                 costs.grouped_gemm(rows, f, d, e)]      # down
    least = sum(costs.roofline_s(fl, by, run.peaks) for fl, by in per_layer)
    least *= (a.n_layers - a.first_dense) * n_steps
    return 100.0 * least / secs


def attention_roofline(run, kernel: str):
    """Least time of the traced steps' attention-kernel calls for the rows
    each slot fed over its cached context, over their device time, in
    percent."""
    if run.trace is None or run.peaks is None:
        return None
    secs, n_ops = tr.kernel_s(run.trace, kernel)
    if not n_ops or not run.rec.steps:
        return None
    a = run.arch
    if a.attention == "mla":
        nq, dqk, dv, nkv = a.n_heads, a.head_dim + a.rope_dim, \
            a.v_head_dim, a.n_heads
    else:
        nq, dqk, dv, nkv = a.n_heads, a.head_dim, a.head_dim, a.n_kv_heads
    least = 0.0
    for s in run.rec.steps:
        fl = by = 0.0
        for q, off in zip(s.q_lens.tolist(), s.offsets.tolist()):
            f_, b_ = costs.attention(q, off, nq, dqk, dv, nkv)
            fl, by = fl + f_, by + b_
        least += costs.roofline_s(fl, by, run.peaks)
    return 100.0 * least * a.n_layers / secs


def queue_wait_mean_ms(run):
    """Mean of due time to admission over the window's requests, in ms."""
    waits = [(r.t_admitted - r.arrival) * 1e3 for r in run.requests
             if r.t_admitted > 0]
    return sum(waits) / len(waits) if waits else None
