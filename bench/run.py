"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The last line of standard output is the result (JSON); the last
lines of standard error are the numbers that decided ``correct``, each
beside its limit.  Without an accelerator, with fewer chips than the cell
asks for, or without ``BENCHMARK.json``, it exits non-zero and prints no
result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("run.py: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "")
    from bench import harness
    harness.enable_cache()
    try:
        result, lines = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except harness.NotRunnable as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
