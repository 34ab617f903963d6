"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
returns plain events; everything after that (busy time, idle gaps, kernel
and program times) works on those events, so it is tested on a small
recorded excerpt without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os

# the serving step's jitted program, as XLA names its module
STEP_PROGRAM = "_unified_impl"
# host spans the harness records around its calls into the engine
HOST_SPANS = ("bench.step", "bench.admit")
# other host events (the runtime's and JAX's own) are kept from this long,
# to name what the host was doing in a long idle gap
HOST_EVENT_S = 0.05


@dataclasses.dataclass(frozen=True)
class Event:
    name: str          # HLO instruction ("flash_chunk_paged.10"), program
    #                    or host span name
    start: float       # seconds, on the trace's clock
    dur: float         # seconds


@dataclasses.dataclass
class Trace:
    ops: list          # device ops of one chip (XLA Ops line)
    modules: list      # device programs of that chip (XLA Modules line)
    spans: list        # the harness's host spans
    host: list = dataclasses.field(default_factory=list)
    #                    other host events of HOST_EVENT_S or longer


def instruction(text: str) -> str:
    """'%fusion.210 = bf16[...] fusion(...)' -> 'fusion.210'."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def op_kind(name: str) -> str:
    """'flash_chunk_paged.10' -> 'flash_chunk_paged': a Pallas kernel's
    custom call is named after the function that wraps it."""
    base, _, tail = name.rpartition(".")
    return base if tail.isdigit() and base else name


def load(trace_dir: str, device: str = "/device:TPU:0") -> Trace:
    """The ops and programs of ``device`` and the harness's host spans."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    ops, modules, spans, host = [], [], [], []
    for plane in pd.planes:
        on_device = plane.name == device
        on_host = plane.name.startswith("/host:")
        if not (on_device or on_host):
            continue
        for line in plane.lines:
            if on_device and line.name == "XLA Ops":
                ops.extend(Event(instruction(ev.name), ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9) for ev in line.events)
            elif on_device and line.name == "XLA Modules":
                modules.extend(Event(ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9)
                               for ev in line.events)
            elif on_host:
                for ev in line.events:
                    e = Event(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                    if e.name in HOST_SPANS:
                        spans.append(e)
                    elif e.dur >= HOST_EVENT_S:
                        host.append(e)
    key = lambda e: e.start
    return Trace(sorted(ops, key=key), sorted(modules, key=key),
                 sorted(spans, key=key), sorted(host, key=key))


def union(events) -> list:
    """Merged [start, end) intervals covered by ``events``."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = e.start, e.start + e.dur
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_s(tr: Trace) -> float:
    return sum(t - s for s, t in union(tr.ops))


def window(tr: Trace) -> tuple:
    """The traced window: from the first to the last event of any kind."""
    evs = tr.ops + tr.spans + tr.modules
    return (min(e.start for e in evs),
            max(e.start + e.dur for e in evs))


def kernel_s(tr: Trace, kernel: str) -> tuple:
    """(seconds, calls) of ``kernel``'s custom calls on the device."""
    evs = [e for e in tr.ops if op_kind(e.name) == kernel]
    return sum(e.dur for e in evs), len(evs)


def program_steps(tr: Trace) -> list:
    return [e for e in tr.modules if STEP_PROGRAM in e.name]


# ops that contain other ops (a scan's loop, a branch): their time is
# their children's, so they are left out of the top list
CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, n: int = 10) -> list:
    """The instructions that took the most device time, summed over their
    runs (an instruction inside the layer loop runs once per layer)."""
    tot: dict = {}
    for e in tr.ops:
        if op_kind(e.name) in CONTAINERS:
            continue
        tot[e.name] = tot.get(e.name, 0.0) + e.dur
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def _covering(events, t: float):
    """The shortest of ``events`` that covers the instant ``t``."""
    return min((e for e in events if e.start <= t <= e.start + e.dur),
               key=lambda e: e.dur, default=None)


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The longest stretches with no device op, each named by the host
    span that covers its middle (no span: the serving loop outside the
    engine, waiting for arrivals included), and after " > " by the
    innermost longer host event there, if any (``np.asarray(jax.Array)``,
    a transfer, a dispatch)."""
    busy = union(tr.ops)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] > a[1]]
    gaps.sort(key=lambda g: -g[0])
    out = []
    for length, s, t in gaps[:n]:
        mid = 0.5 * (s + t)
        span = _covering(tr.spans, mid)
        name = span.name if span else "host.loop"
        inner = _covering(tr.host, mid)
        out.append([name + (f" > {inner.name}" if inner else ""), length])
    return out
