"""One run of one cell: set-up, a measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

The window drives the serving system's own entry, ``repro.serving.api.LLM``
with every knob left on ``"auto"``: an open-loop mix goes through the
``Scheduler`` (each request carries its due time, and the scheduler admits
it when due), a closed queue through ``LLM.submit`` and ``LLM.stream``.
The harness records host spans and token times around its calls into the
engine, and nothing inside the program.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

from bench import arch as archmod
from bench import traffic as trafficmod

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
MAX_PACKED = 16384        # prompt + served tokens the reference packs
LONG_STEP_S = 0.5         # an engine step this long is a stall, logged


class NotRunnable(RuntimeError):
    """No accelerator, fewer chips than the cell asks for, no
    BENCHMARK.json, or no such cell in it."""


def load_benchmark(root=ROOT) -> dict:
    path = pathlib.Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise NotRunnable(f"{path} is missing")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> tuple:
    """(cell, configuration entry, metrics of this cell) for ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NotRunnable(f"no workload {name!r} (have {sorted(cells)})")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return cell, conf, e2e, layer


def metric_reader(name: str):
    """The reader of a per-layer metric: ``read`` of ``metrics/<name>.py``,
    or else of ``metrics/<stem>.py``, the stem being the name before its
    first dot (``step_ms.chat`` and ``step_ms.offline`` share
    ``metrics/step_ms.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def pct(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# recording around the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    t0: float             # host clock before the engine step
    t1: float             # host clock after it returned (tokens read back)
    q_lens: np.ndarray    # rows each slot fed
    offsets: np.ndarray   # each slot's cached rows before the step


class Recorder:
    """Wraps ``engine.unified_step`` and ``engine.admit`` with host spans
    and keeps every step's plan and every token's host time."""

    def __init__(self, engine):
        import jax
        self.engine = engine
        self.steps: list = []
        self.token_t: dict = {}
        self.admitted: dict = {}      # rid -> Request, in admission order
        self._span = jax.profiler.TraceAnnotation
        self._dev = jax.devices()[0]
        self.long_steps: list = []    # (t0, t1, rows, device memory stats)
        self._step, self._admit = engine.unified_step, engine.admit
        engine.unified_step, engine.admit = self._rec_step, self._rec_admit

    def _rec_step(self, q_lens):
        eng = self.engine
        lengths = getattr(eng.kv, "lengths", None)
        offs = (np.asarray(lengths).copy() if lengths is not None
                else np.zeros(len(eng.slots), np.int64))
        before = [(r, len(r.out_tokens)) for r in eng.slots if r is not None]
        t0 = time.perf_counter()
        with self._span("bench.step"):
            out = self._step(q_lens)
        t1 = time.perf_counter()
        if t1 - t0 > LONG_STEP_S:
            self.long_steps.append((t0, t1, int(np.sum(q_lens)),
                                    self._dev.memory_stats() or {}))
        for r, n0 in before:
            n = len(r.out_tokens) - n0
            if n > 0:
                self.token_t.setdefault(r.rid, []).extend([t1] * n)
        self.steps.append(Step(t0, t1, np.asarray(q_lens, np.int32).copy(),
                               offs))
        return out

    def _rec_admit(self, req):
        with self._span("bench.admit"):
            ok = self._admit(req)
        if ok:
            self.admitted[req.rid] = req
        return ok

    def reset(self):
        self.steps, self.token_t, self.admitted = [], {}, {}
        self.long_steps = []


class GcWatch:
    """Python's garbage collections while the window runs, each with its
    pause and generation, for the stall line on standard error."""

    def __enter__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._cb)
        return self

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((time.perf_counter() - self._t,
                                info["generation"]))
            self._t = None

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def stall_line(steps: list, gcs: list, long_steps: list,
               t_open: float) -> str:
    """Where the host held the window up: its longest engine step and
    longest gap between two steps, the garbage collector's pauses, and
    each step over LONG_STEP_S with its time in the window, its rows and
    the device's memory then."""
    dur = [s.t1 - s.t0 for s in steps]
    gaps = [b.t0 - a.t1 for a, b in zip(steps, steps[1:])]
    worst = max(gcs, default=(0.0, 0))
    longs = "; ".join(", ".join(
        [f"{t0 - t_open:.2f} s into the window, {t1 - t0:.3f} s, {rows} rows"]
        + [f"{k} {v}" for k, v in sorted(mem.items())])
        for t0, t1, rows, mem in long_steps)
    return (f"host stalls: {len(steps)} steps, median "
            f"{1e3 * float(np.median(dur or [0])):.1f} ms, longest "
            f"{1e3 * max(dur, default=0):.1f} ms; longest gap between steps "
            f"{1e3 * max(gaps, default=0):.1f} ms; gc {len(gcs)} collections "
            f"({sum(g == 2 for _, g in gcs)} of generation 2), "
            f"{1e3 * sum(p for p, _ in gcs):.1f} ms in all, longest "
            f"{1e3 * worst[0]:.1f} ms (generation {worst[1]}); steps over "
            f"{LONG_STEP_S} s: {longs or 'none'}")


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

RID0 = 1_000_000           # window request ids, apart from warm-up ids


def _requests(items, rid0: int = RID0):
    from repro.serving.engine import Request
    return [Request(rid=rid0 + i, prompt=it.prompt, max_new_tokens=it.n_out,
                    arrival=it.due) for i, it in enumerate(items)]


def serve_open(llm, items, seconds: float):
    """Open loop: every request handed to the scheduler with its due time
    (offsets from the window's open); the scheduler admits each when due.
    Runs until every request due in the window has finished.  Returns the
    requests, the window's open and close, the failed count and the
    generator's lateness: for each request, how long after its due time
    the serving loop first read its clock (the loop is the generator)."""
    from repro.serving.scheduler import Scheduler
    sched = Scheduler(llm.engine)
    reqs = _requests(items)
    for r in reqs:
        sched.submit(r)                 # a shed request counts as failed
    ticks, clock = [], sched._now

    def now():
        t = clock()
        ticks.append(t)
        return t

    sched._now = now
    t_open = time.perf_counter()
    sched.run(max_steps=10 ** 9)        # rebases each due time onto its
    #                                     own clock read at its start
    t_close = t_open + seconds
    failed = len(sched.shed) + len(sched.failed) + len(sched.cancelled)
    ticks = np.asarray(ticks)
    due = np.asarray([r.arrival for r in reqs])
    seen = ticks[np.minimum(np.searchsorted(ticks, due), len(ticks) - 1)]
    return reqs, t_open, t_close, failed, np.maximum(seen - due, 0.0)


def serve_closed(llm, items, seconds: float):
    """Closed queue: every request submitted before the window opens;
    the stream runs until the window closes."""
    for it in items:
        llm.submit(it.prompt, it.n_out)
    t_open = time.perf_counter()
    t_close = t_open + seconds
    for _ in llm.stream():
        if time.perf_counter() >= t_close:
            break
    return t_open, t_close


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(done, seed: int) -> list:
    """Requests to compare: the one with the most served tokens, then the
    others in an order drawn from the seed, each that still fits in
    MAX_PACKED prompt and served tokens.  Every finished request is taken
    while they fit, so that as many rows as a run has are compared where
    routing near-ties (see TIE_MARGIN) leave some out."""
    done = sorted(done, key=lambda r: r.rid)
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 7])
    longest = max(done, key=lambda r: (len(r.out_tokens), -r.rid))
    pick = [longest]
    packed = len(longest.prompt) + len(longest.out_tokens)
    for i in rng.permutation(len(done)):
        r = done[i]
        n = len(r.prompt) + len(r.out_tokens)
        if r is longest or packed + n > MAX_PACKED:
            continue
        pick.append(r)
        packed += n
    return pick


# A served token whose predicting row had, in some MoE layer, its k-th and
# (k+1)-th router logits closer than this is a routing near-tie: bf16
# rounding may pick the other expert set, which moves that row by O(1) and
# its gap with it (chip runs read such flips at margins up to ~0.017).
# Such rows are counted (tie_share) and left out of mean_gap/widest_gap;
# the *_all numbers keep them.
TIE_MARGIN = 0.02


def judge(stats, failed: int, limits: dict) -> tuple:
    """(correct, compared): every number of ``limits`` in ``stats`` at or
    under its limit, and no failed request.  ``stats`` None (nothing
    finished to compare) is not correct."""
    compared = {}
    ok = stats is not None and failed == 0
    if stats is not None:
        for name, limit in limits.items():
            compared[name] = {"value": stats[name], "limit": limit}
            ok = ok and stats[name] <= limit
    compared["failed_requests"] = {"value": failed, "limit": 0}
    return bool(ok), compared


def gap_stats(gaps_margins) -> dict:
    """The served tokens' gaps, over the rows that are not routing
    near-ties and over all rows, with the routing margin of the row that
    reads the widest untied gap (a margin just over TIE_MARGIN there
    points to a routing flip, a wide one to an error in the program)."""
    g, m = (np.asarray(v, np.float64) for v in gaps_margins)
    keep = m >= TIE_MARGIN
    untied = g[keep] if keep.any() else np.zeros(1)
    worst = int(np.argmax(np.where(keep, g, -1.0)))
    return {"tokens": int(g.size), "untied": int(keep.sum()),
            "tie_share": float(1.0 - keep.mean()),
            "mean_gap": float(untied.mean()),
            "widest_gap": float(untied.max()),
            "widest_margin": float(m[worst]) if keep.any() else 0.0,
            "mean_gap_all": float(g.mean()), "widest_gap_all": float(g.max()),
            "off_argmax": float(np.mean(g > 0))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _devices(chips: int, require_chip: bool):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NotRunnable(f"no accelerator: {e}") from None
    if require_chip:
        if devs[0].platform != "tpu":
            raise NotRunnable(f"no TPU found (JAX platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NotRunnable(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


def enable_cache():
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``), for every program however short
    its compile.  Entry points call this; a test process keeps the setting
    it started with."""
    import jax

    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def overrides(a) -> dict:
    """ServeSpec fields that the configuration's file sets away from
    "auto", each with its reason there (``serve_overrides``)."""
    ov = a.overrides
    if not ov:
        return {}
    import dataclasses as dc

    from repro.kernels.policy import KernelPolicy
    off = {k: False for k in ov["kernels_off"]}
    return {"kernels": dc.replace(KernelPolicy.auto(), **off)}


def build_system(a, mix: dict, seed: int, split: dict):
    """Weights drawn on the device, the serving system on auto, warmed up
    on the one step shape the cell uses.  Returns (llm, recorder)."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import abstract_params
    from repro.serving.api import LLM, ServeSpec

    from bench.weights import Weights
    t = time.perf_counter()

    # ---- weights: one jitted call on the device, from the seed ----
    cfg = archmod.program_config(a)
    params = Weights(a, seed).tree_for(abstract_params(cfg, jnp.bfloat16))
    jax.block_until_ready(params)
    split["weights"] = time.perf_counter() - t
    t = time.perf_counter()

    # ---- the serving system, every knob on auto ----
    spec = ServeSpec(reduced=False, seed=int(seed) % (2 ** 31),
                     **trafficmod.hints(mix), **overrides(a))
    llm = LLM.from_spec(spec, cfg=cfg, params=params, dtype=jnp.bfloat16)
    del params
    eng = llm.engine
    rec = Recorder(eng)
    split["build"] = time.perf_counter() - t
    t = time.perf_counter()

    # ---- warm-up: the cell's one step shape, admission and release ----
    wrng = np.random.default_rng([int(seed), 11])
    c = eng.chunk
    warm = [wrng.integers(0, a.vocab, size=n).astype(np.int32)
            for n in (min(2 * c + 5, eng.max_len - 4), 3)]
    llm.generate(warm[:1], 2)
    split["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    llm.generate(warm, 4)
    split["warmup"] = time.perf_counter() - t
    return llm, rec


@dataclasses.dataclass
class Run:
    """What one run saw: the per-layer metric readers take this."""
    arch: object
    spec: object
    peaks: object
    rec: Recorder
    requests: list
    t_open: float
    t_close: float
    trace: object = None


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root=ROOT, require_chip: bool = True,
             bench: dict = None, breaker=None, on_sample=None) -> tuple:
    """One run; returns (the result line's object, the lines for standard
    error).  ``breaker`` (tests only) breaks the timed path under the
    window, to see ``correct`` come out false; ``on_sample`` (the control
    tool) is called with (arch, seed, sampled requests) once the program's
    state is gone."""
    bench = bench if bench is not None else load_benchmark(root)
    cell, conf, e2e, layer = find_cell(bench, cell_name)
    conf_file = archmod.load_config(pathlib.Path(root) / conf["file"])
    a = archmod.arch_from(conf_file)
    mix = json.loads((pathlib.Path(root) / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    split = {}
    t = time.perf_counter()
    split["imports"] = t - t_start

    # ---- backend ----
    devs = _devices(cell["chips"], require_chip)
    import jax

    from bench.peaks import peaks_for
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_chip else None
    split["backend"] = time.perf_counter() - t
    t = time.perf_counter()

    llm, rec = build_system(a, mix, seed, split)
    eng = llm.engine
    t = time.perf_counter()
    items = trafficmod.schedule(mix, a.vocab, seed, seconds)
    rec.reset()
    if breaker is not None:
        breaker(eng)
    split["schedule"] = time.perf_counter() - t
    t = time.perf_counter()
    gc.collect()            # set-up's own garbage, collected in set-up
    split["gc"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    # ---- the window ----
    from repro.analysis.compile_watch import CompileWatch
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with CompileWatch() as watch, GcWatch() as gcw:
        if mix["kind"] == "open":
            reqs, t_open, t_close, failed, late = serve_open(
                llm, items, seconds)
        else:
            t_open, t_close = serve_closed(llm, items, seconds)
            late = np.zeros(1)          # every request queued before
            reqs = list(rec.admitted.values())
            failed = sum(r.terminal and r.state != "DONE" for r in reqs)
        attempted = len(reqs)
    if trace:
        jax.profiler.stop_trace()
    run = Run(a, eng.spec, peaks, rec, reqs, t_open, t_close)

    # ---- device memory, then the program's state goes ----
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    done = [r for r in reqs if r.state == "DONE"]
    if mix["kind"] == "closed":
        done = [r for r in done if r.t_done <= t_close]
    picked = [(np.asarray(r.prompt), list(r.out_tokens))
              for r in sample(done, seed)]

    lines = []
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from bench import trace as tr
        run.trace = tr.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        w0, w1 = tr.window(run.trace)
        device["busy_s"] = tr.busy_s(run.trace)
        device["window_s"] = w1 - w0
        metrics = {}
        for m in layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(run.trace),
                     "idle_gaps": tr.idle_gaps(run.trace)}
    else:
        metrics = e2e_metrics(run, e2e)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    lines.append("setup split (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in split.items()) + f"; total {setup_s:.3f}")
    lines.append(
        f"traffic: {attempted} requests, {failed} failed, "
        f"{len(done)} finished; generator lateness (due time to the "
        f"serving loop's first clock read after it) median "
        f"{1e3 * float(np.median(late)):.1f} ms, max "
        f"{1e3 * float(np.max(late)):.1f} ms; compiles in window "
        f"{len(watch.events)}; resolved: chunk {eng.chunk}, max_batch "
        f"{eng.max_batch}, token_budget {eng.spec.token_budget}, max_len "
        f"{eng.max_len}, kernels {eng.spec.kernels.describe()}")
    lines.append(stall_line(
        [s for s in rec.steps if s.t1 <= t_close], gcw.pauses,
        rec.long_steps, t_open))
    del llm, eng, rec.engine, rec._step, rec._admit, run
    gc.collect()

    # ---- correctness, against the reference, after the program is gone ----
    from bench.reference import served_gaps
    limits = conf_file["correct"]
    t = time.perf_counter()
    stats = gap_stats(served_gaps(a, seed, picked)) if picked else None
    ref_s = time.perf_counter() - t
    if on_sample is not None:
        on_sample(a, seed, picked)
    ok, compared = judge(stats, failed, limits)
    lines.append(
        f"reference: {len(picked)} requests, "
        f"{stats['tokens'] if stats else 0} served tokens, {ref_s:.1f} s; "
        + (", ".join(f"{k} {v:.6g}" for k, v in stats.items())
           if stats else "nothing finished to compare"))
    lines.extend(f"compared: {k} {v['value']} limit {v['limit']}"
                 for k, v in compared.items())
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result, lines


def e2e_metrics(run: Run, e2e: list) -> dict:
    out = {}
    names = {m["name"]: m for m in e2e}
    reqs = [r for r in run.requests if r.state == "DONE"]
    if "ttft_mean_ms" in names:
        ttft = [(r.t_first_token - r.arrival) * 1e3 for r in reqs]
        out["ttft_mean_ms"] = {"value": float(np.mean(ttft)), "unit": "ms"}
    if "itl_p95_ms" in names:
        gaps = []
        for r in reqs:
            ts = run.rec.token_t.get(r.rid, [])
            gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        out["itl_p95_ms"] = {"value": pct(gaps, 95), "unit": "ms"}
    if "output_tok_s" in names:
        n = sum(sum(1 for t in ts if run.t_open <= t <= run.t_close)
                for ts in run.rec.token_t.values())
        out["output_tok_s"] = {"value": n / (run.t_close - run.t_open),
                               "unit": "tokens/s"}
    return out
