"""The serving program's own profiler spans, and the device's idle time cut
by them.

The engine and the scheduler record spans named ``engine.*`` and
``serve.*`` (docs/serving.md "Tracing"), with integer args, on the
profiler's clock, which is the device ops' clock.  ``load_program`` reads
them from a trace directory; ``idle_by_span`` cuts every device-idle
stretch of the traced window by the innermost program span over it, so
that the idle time splits into host phases, the read-back wait, the wait
for arrivals, and what no span covers.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs one traced run of a cell, as ``bench/run.py --trace 1`` does, and
prints the split as one JSON line: idle ms per step under each span, the
host time per step of each span, the three readings below, and the run's
own result line.  ``--record <file.xplane.pb.gz>`` instead serves two
short requests on the cell's configuration under the profiler and keeps
the trace, a few steps long, for the tests.
"""

from __future__ import annotations

import dataclasses
import glob
import os

PREFIXES = ("engine.", "serve.")
READBACK = "engine.readback"
WAIT = "serve.wait"
# host work outside the engine step that the device waits on
LOOP_SPANS = ("serve.admit", "serve.deadlines")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float       # seconds, on the trace's clock
    dur: float         # seconds
    args: dict


def load_program(trace_dir: str) -> list:
    """The ``engine.*`` and ``serve.*`` host spans of the newest
    ``.xplane.pb`` under ``trace_dir``, with their args, by start."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.name, ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda s: s.start)


def idle_intervals(trace) -> list:
    """[start, end) stretches of the traced window with no device op."""
    from bench import trace as tr
    w0, w1 = tr.window(trace)
    out, t = [], w0
    for s, e in tr.union(trace.ops):
        if s > t:
            out.append((t, min(s, w1)))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return [(s, e) for s, e in out if e > s]


def innermost(program: list, w0: float, w1: float) -> list:
    """Disjoint (start, end, name) stretches of [w0, w1) that some span
    covers, each named by the shortest span over it (for spans of one
    thread, the innermost), in order."""
    spans = sorted((s for s in program
                    if s.dur > 0 and s.start < w1 and s.start + s.dur > w0),
                   key=lambda s: s.start)
    pts = sorted({w0, w1} | {min(w1, max(w0, x)) for s in spans
                             for x in (s.start, s.start + s.dur)})
    out, active, j = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while j < len(spans) and spans[j].start <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s.start + s.dur > a]
        if not active:
            continue
        name = min(active, key=lambda s: s.dur).name
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(trace, program: list) -> dict:
    """Seconds of device idle in the traced window under each span name,
    the innermost span deciding; ``""`` holds the idle no span covers.
    The values sum to the window's idle time."""
    from bench import trace as tr
    idle = idle_intervals(trace)
    segs = innermost(program, *tr.window(trace))
    out: dict = {}
    i = j = 0
    while i < len(idle) and j < len(segs):
        lo = max(idle[i][0], segs[j][0])
        hi = min(idle[i][1], segs[j][1])
        if hi > lo:
            out[segs[j][2]] = out.get(segs[j][2], 0.0) + hi - lo
        if idle[i][1] <= segs[j][1]:
            i += 1
        else:
            j += 1
    total = sum(e - s for s, e in idle)
    out[""] = total - sum(out.values())
    return out


def is_host_phase(name: str) -> bool:
    """The engine's and the loop's host work: every ``engine.*`` span but
    the read-back, and admission and deadline checks."""
    return (name.startswith("engine.") and name != READBACK) \
        or name in LOOP_SPANS


def _per_step_ms(trace, seconds: float):
    from bench import trace as tr
    n = len(tr.program_steps(trace))
    return 1e3 * seconds / n if n else None


def idle_host_ms(trace, program: list):
    """Device idle under the host phases, ms per step program."""
    if not program:
        return None
    split = idle_by_span(trace, program)
    return _per_step_ms(trace, sum(v for k, v in split.items()
                                   if is_host_phase(k)))


def idle_readback_ms(trace, program: list):
    """Device idle under ``engine.readback``, ms per step program."""
    if not program:
        return None
    return _per_step_ms(trace, idle_by_span(trace, program).get(READBACK,
                                                                 0.0))


def fed_row_share(trace, program: list):
    """Rows fed over rows computed, percent, over the window's
    ``engine.step`` spans that ran a program (``rows`` > 0)."""
    from bench import trace as tr
    w0, w1 = tr.window(trace)
    steps = [s for s in program if s.name == "engine.step"
             and w0 <= s.start <= w1 and s.args.get("rows", 0) > 0]
    if not steps:
        return None
    return 100.0 * sum(s.args["rows"] for s in steps) \
        / sum(s.args["buffer"] for s in steps)


def host_ms_by_span(trace, program: list) -> dict:
    """Host time per step program of each span name in the window, ms,
    and ``engine.step (self)``: the step's time outside its children."""
    from bench import trace as tr
    w0, w1 = tr.window(trace)
    inside = [s for s in program if w0 <= s.start <= w1]
    tot: dict = {}
    for s in inside:
        tot[s.name] = tot.get(s.name, 0.0) + s.dur
    child = sum(v for k, v in tot.items()
                if k.startswith("engine.") and k != "engine.step")
    if "engine.step" in tot:
        tot["engine.step (self)"] = tot["engine.step"] - child
    return {k: _per_step_ms(trace, v) for k, v in sorted(tot.items())}


def split(trace, program: list) -> dict:
    """Every reading of this module for one traced run."""
    from bench import trace as tr
    w0, w1 = tr.window(trace)
    by = idle_by_span(trace, program)
    idle = sum(by.values())
    return {"window_s": w1 - w0, "idle_s": idle,
            "steps": len(tr.program_steps(trace)),
            "idle_host_ms": idle_host_ms(trace, program),
            "idle_readback_ms": idle_readback_ms(trace, program),
            "fed_row_share": fed_row_share(trace, program),
            "idle_wait_ms": _per_step_ms(trace, by.get(WAIT, 0.0)),
            "uncovered_share": by[""] / idle if idle else None,
            "idle_ms_by_span": {k or "(no span)": _per_step_ms(trace, v)
                                for k, v in sorted(by.items())},
            "host_ms_by_span": host_ms_by_span(trace, program)}


# ---------------------------------------------------------------------------
# command line
# ---------------------------------------------------------------------------

def _traced_run(cell: str, seed: int, seconds: float, t_start: float,
                **kw):
    """``harness.run_cell`` with the trace on (``kw`` goes to it); the
    program spans are read from its trace directory before the harness
    removes it."""
    from bench import harness
    from bench import trace as tr
    kept = {}
    load = tr.load

    def load_both(trace_dir, *a, **k):
        kept["program"] = load_program(trace_dir)
        kept["trace"] = load(trace_dir, *a, **k)
        return kept["trace"]

    tr.load = load_both
    try:
        result, lines = harness.run_cell(cell, seed, seconds, True,
                                         t_start=t_start, **kw)
    finally:
        tr.load = load
    return result, lines, split(kept["trace"], kept["program"])


def _record(cell: str, seed: int, out: str, *, root=None,
            require_chip: bool = True, bench: dict = None) -> dict:
    """Two short requests on the cell's configuration, traced as the
    harness traces; the trace goes gzipped to ``out``."""
    import gzip
    import json
    import pathlib
    import shutil
    import tempfile

    import jax
    import numpy as np

    from bench import arch as archmod
    from bench import harness
    from bench import traffic as trafficmod
    root = pathlib.Path(root or harness.ROOT)
    bench = bench or harness.load_benchmark(root)
    c, conf, _, _ = harness.find_cell(bench, cell)
    a = archmod.arch_from(archmod.load_config(root / conf["file"]))
    mix = json.loads((root / "bench" / "traffic"
                      / f"{c['traffic']}.json").read_text())
    harness._devices(c["chips"], require_chip)
    llm, rec = harness.build_system(a, mix, seed, {})
    rng = np.random.default_rng([int(seed), 13])
    prompts = [rng.integers(0, a.vocab, size=n).astype(np.int32)
               for n in (min(70, llm.engine.max_len - 8), 6)]
    rec.reset()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    llm.generate(prompts, 3)
    jax.profiler.stop_trace()
    src = max(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True), key=os.path.getmtime)
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(src, "rb") as f, gzip.open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tdir, ignore_errors=True)
    return {"recorded": out, "steps": [
        [s.t0, s.t1, s.q_lens.tolist(), s.offsets.tolist()]
        for s in rec.steps]}


def main(argv=None) -> int:
    import time
    t_start = time.perf_counter()
    import argparse
    import json
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--record", help="keep a short trace here instead")
    args = ap.parse_args(argv)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "")
    from bench import harness
    harness.enable_cache()
    try:
        if args.record:
            print(json.dumps(_record(args.workload, args.seed, args.record)))
            return 0
        result, lines, parts = _traced_run(args.workload, args.seed,
                                           args.seconds, t_start)
    except harness.NotRunnable as e:
        print(f"spans.py: {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps({"split": parts, "result": result}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
