"""Run one cell of BENCHMARK.json on the chip; print its result as the last line.

    python3 bench/run.py --workload abpn_x3_1080p.live_60fps --seed 7 \\
        --seconds 10 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run that also records a profiler trace.  There
is no CPU path: without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import run_cell

    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
