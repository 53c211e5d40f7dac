"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (per the repo contract) and a
summary of the roofline artifacts if a dry-run sweep exists.
"""

from __future__ import annotations

import os
import sys

# Allow ``python benchmarks/run.py`` from anywhere: the repo root (parent of
# this file's directory) must be importable for ``from benchmarks import …``.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main() -> None:
    from benchmarks import (
        bandwidth_reduction,
        kernel_micro,
        psnr_penalty,
        table1_throughput,
        table2_buffers,
    )

    print("name,us_per_call,derived")
    modules = [table1_throughput, table2_buffers, bandwidth_reduction,
               psnr_penalty, kernel_micro]
    for mod in modules:
        for name, us, derived in mod.rows():
            print(f'{name},{us:.1f},"{derived}"')

    # roofline summary (if the dry-run sweep has been run)
    try:
        from repro.roofline.report import load_records, roofline_row

        recs = [r for r in load_records()
                if r.get("mesh") == "single_pod" and r.get("status") == "ok"]
        rows = [roofline_row(r) for r in recs]
        rows = [r for r in rows if r]
        if rows:
            best = max(rows, key=lambda r: r["roofline_fraction"])
            print(f'roofline.cells_ok,{0.0:.1f},"{len(rows)} single-pod cells"')
            print(f'roofline.best_fraction,{0.0:.1f},'
                  f'"{best["roofline_fraction"]:.3f} ({best["arch"]} x '
                  f'{best["shape"]})"')
    except Exception as e:  # sweep not run yet — benchmarks still valid
        print(f'roofline.summary,0.0,"unavailable: {e}"', file=sys.stderr)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
