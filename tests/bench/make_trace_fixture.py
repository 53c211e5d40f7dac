"""Cut a committed fixture from a trace recorded on the chip.

    python3 tests/bench/make_trace_fixture.py <trace_dir> <chips> <from_ms> <ms> <out.json.gz>

Keeps ``ms`` milliseconds from ``from_ms`` after the trace's
``bench.trace_begin`` marker: the cell's device operations and the benchmark's host spans that
overlap them, with the markers moved to the slice's edges, and the
reduction's own numbers on the slice as ``expect``.
"""

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402


def main() -> int:
    trace_dir, chips, out = sys.argv[1], int(sys.argv[2]), sys.argv[5]
    start_ms, ms = float(sys.argv[3]), float(sys.argv[4])
    t = tr.load(tr.find_xplane(trace_dir))
    lo = next(s for n, s, _ in t["host"] if n == tr.BEGIN) + start_ms * 1e6
    hi = lo + ms * 1e6
    keep = lambda s, e: e > lo and s < hi  # noqa: E731
    ids = sorted(t["devices"])[:chips]
    cut = {
        "devices": {d: [[n, s, e] for n, s, e in t["devices"][d] if keep(s, e)] for d in ids},
        "host": [[tr.BEGIN, lo, lo], [tr.END, hi, hi]]
        + [[n, s, e] for n, s, e in t["host"] if keep(s, e) and n not in (tr.BEGIN, tr.END)],
    }
    r = tr.reduce({"devices": {d: [tuple(x) for x in v] for d, v in cut["devices"].items()},
                   "host": [tuple(x) for x in cut["host"]]}, chips)
    cut["expect"] = {k: r[k] for k in ("window_s", "busy_s", "busy_s_per_chip")}
    with gzip.open(out, "wt") as f:
        json.dump(cut, f)
    print(json.dumps(cut["expect"]), sum(len(v) for v in cut["devices"].values()), "ops")
    return 0


if __name__ == "__main__":
    sys.exit(main())
