"""Readings for a cell's limits: the program and its control over many
seeds, in one process (set-up paid once per seed, JAX once).

    python3 bench/readings.py --workload abpn_x3_1080p.live_60fps \\
        --seeds 12 --control-seeds 3 --seconds 3

Each seed runs the cell as ``bench/run.py`` does, at the cell's own size
and load, with a short window; the control runs the program with its
configuration's ``control`` keys (a lower precision) in place.  Prints one
line per run and, last, a JSON summary of every number compared.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()

    from bench import spec
    from bench.harness import run_cell

    bench = spec.load_benchmark()
    cfg = spec.config(bench, spec.workload(bench, args.workload)["config"])
    runs = [("program", None, args.first_seed + k) for k in range(args.seeds)]
    runs += [("control", cfg["control"], args.first_seed + k)
             for k in range(args.control_seeds)]
    summary = {"program": [], "control": []}
    for kind, overrides, seed in runs:
        r = run_cell(args.workload, seed, args.seconds, False,
                     t_start=time.monotonic(), bench=bench, overrides=overrides)
        numbers = {k: v["value"] for k, v in r["check"].items()}
        summary[kind].append({"seed": seed, "failed": r["failed"], **numbers})
        print(f"readings {kind} seed={seed} failed={r['failed']} "
              + " ".join(f"{k}={v!r}" for k, v in numbers.items()), flush=True)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
