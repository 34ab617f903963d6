"""The knee of an open-loop cell: the highest offered rate the system
sustains, found once by a sweep on the chip at the cell's window length.

    python3 bench/knee.py --workload <cell> --seconds 51 --rates 2 3 4 5 \
        [--runs 2] [--out sweep.jsonl]

One set-up, then ``--runs`` windows per rate with the cell's traffic mix at
that rate.  The rates serve prefixes of one list of sizes and arrivals
(``traffic.schedule``); each window draws its token ids from its own seed
(``--seed`` plus the window's index), so the prefix cache holds nothing an
earlier window sent.  A window is sustained when no queue builds: no
request failed, none that fell due in the window still waits for admission
at its close, and the 95th percentile of the queue wait (due time to
admission) is at most ``WAIT_S``; a rate is sustained when each of its
windows is.  The cell's traffic file then fixes 0.8 of the highest
sustained rate.  One JSON line per window, also appended to ``--out`` when
given.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

WAIT_S = 1.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", help="JSONL file to append each line to")
    args = ap.parse_args(argv)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "")
    import numpy as np

    from bench import arch as archmod
    from bench import harness
    from bench import traffic as trafficmod
    bench = harness.load_benchmark()
    cell, conf, _, _ = harness.find_cell(bench, args.workload)
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    if mix["kind"] != "open":
        print("knee.py: only open-loop cells have a knee", file=sys.stderr)
        return 2
    a = archmod.arch_from(archmod.load_config(ROOT / conf["file"]))
    harness._devices(cell["chips"], True)
    harness.enable_cache()
    split = {}
    llm, rec = harness.build_system(a, mix, args.seed, split)
    windows = [(rate, j) for rate in args.rates for j in range(args.runs)]
    for i, (rate, j) in enumerate(windows):
        m = dict(mix, rate_rps=rate)
        items = trafficmod.schedule(m, a.vocab, args.seed + i, args.seconds)
        rec.reset()
        gc.collect()
        reqs, t_open, t_close, failed, late = harness.serve_open(
            llm, items, args.seconds)
        done = [r for r in reqs if r.state == "DONE"]
        ttft = np.array([r.t_first_token - r.arrival for r in done])
        wait = np.array([r.t_admitted - r.arrival for r in done])
        backlog = sum(r.t_admitted > t_close for r in reqs)
        row = {"workload": args.workload, "rate_rps": rate, "run": j,
               "requests": len(reqs), "failed": failed,
               "ttft_mean_ms": float(np.mean(ttft) * 1e3),
               "ttft_p50_ms": float(np.median(ttft) * 1e3),
               "ttft_max_ms": float(np.max(ttft) * 1e3),
               "queue_wait_mean_ms": float(np.mean(wait) * 1e3),
               "queue_wait_p95_ms": harness.pct(wait * 1e3, 95),
               "backlog_at_close": backlog,
               "drain_s": max(r.t_done for r in done) - t_close,
               "steps": len(rec.steps),
               "lateness_max_ms": float(late.max() * 1e3),
               "sustained": bool(failed == 0 and backlog == 0
                                 and harness.pct(wait, 95) <= WAIT_S)}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
