"""Readings for the limit of each configuration's comparison: the program's
gaps over many seeds, and the float8 control's over the same samples.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--out readings.jsonl]

One process: each seed is a full run of the cell (set-up, window, the
reference's comparison), then the control, the reference computed with
float8 inputs to every matrix product, at the same positions of the same
sampled requests, judged by the configuration's limits with the run's own
``harness.judge``.  Prints one JSON line per seed, with both verdicts
(``correct``, ``control_correct``), and appends it to ``--out`` when given.
Exits 1 if the control passed on any seed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", help="JSONL file to append each line to")
    args = ap.parse_args(argv)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "")
    from bench import harness
    harness.enable_cache()
    from bench.reference import control_gaps
    t = T_START
    control_passed = False
    for seed in args.seeds:
        row = {"workload": args.workload, "seed": seed}

        def on_sample(arch, s, picked):
            if picked:
                t0 = time.perf_counter()
                row["control"] = harness.gap_stats(
                    control_gaps(arch, s, picked))
                row["control_s"] = time.perf_counter() - t0

        result, lines = harness.run_cell(args.workload, seed, args.seconds,
                                         False, t_start=t,
                                         on_sample=on_sample)
        row["correct"] = result["correct"]
        if "control" in row:
            limits = {k: v["limit"] for k, v in result["compared"].items()
                      if k != "failed_requests"}
            ok, compared = harness.judge(row["control"], 0, limits)
            row["control_correct"] = ok
            row["control_compared"] = compared
            control_passed = control_passed or ok
            print(f"seed {seed}: program correct {result['correct']}, "
                  f"control correct {ok}; control " + ", ".join(
                      f"{k} {v['value']} limit {v['limit']}"
                      for k, v in compared.items()), file=sys.stderr)
        row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        row["memory_peak_bytes"] = result["device"]["memory_peak_bytes"]
        row["lines"] = lines
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(row) + "\n")
        t = time.perf_counter()
    return 1 if control_passed else 0


if __name__ == "__main__":
    sys.exit(main())
