"""Cut a committed fixture, program marks included, from a trace recorded on the chip.

    python3 tests/bench/make_program_fixture.py <trace_dir | .xplane.pb> <chips> <from_ms> <ms> <out.json.gz>

Keeps ``ms`` milliseconds from ``from_ms`` after the trace's
``bench.trace_begin`` marker: the cell's device operations, the
benchmark's host spans and the program's ``sr.*`` spans that overlap
them, the named scope of each kept operation, the markers moved to the
slice's edges, and ``bench/trace_program.py``'s numbers on the slice as
``expect``.
"""

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_program as tp, trace_reduce as tr  # noqa: E402

EXPECT = ("window_s", "busy_s", "busy_s_per_chip", "scopes", "idle_gaps", "idle_gaps_program")


def main() -> int:
    src, chips, out = sys.argv[1], int(sys.argv[2]), sys.argv[5]
    start_ms, ms = float(sys.argv[3]), float(sys.argv[4])
    t = tp.load(src if src.endswith(".xplane.pb") else tr.find_xplane(src))
    lo = next(s for n, s, _ in t["host"] if n == tr.BEGIN) + start_ms * 1e6
    hi = lo + ms * 1e6
    keep = lambda s, e: e > lo and s < hi  # noqa: E731
    ids = sorted(t["devices"])[:chips]
    devices = {d: [[n, s, e] for n, s, e in t["devices"][d] if keep(s, e)] for d in ids}
    names = {n for ops in devices.values() for n, _, _ in ops}
    cut = {
        "devices": devices,
        "host": [[tr.BEGIN, lo, lo], [tr.END, hi, hi]]
        + [[n, s, e] for n, s, e in t["host"] if keep(s, e) and n not in (tr.BEGIN, tr.END)],
        "program": [[n, s, e] for n, s, e in t["program"] if keep(s, e)],
        "op_scopes": {n: scope for n, scope in t["op_scopes"].items() if n in names},
    }
    r = tp.reduce({"devices": {d: [tuple(x) for x in v] for d, v in devices.items()},
                   "host": [tuple(x) for x in cut["host"]],
                   "program": [tuple(x) for x in cut["program"]],
                   "op_scopes": cut["op_scopes"]}, chips)
    cut["expect"] = {k: r[k] for k in EXPECT}
    with gzip.open(out, "wt") as f:
        json.dump(cut, f)
    print(json.dumps(cut["expect"]), sum(len(v) for v in devices.values()), "ops")
    return 0


if __name__ == "__main__":
    sys.exit(main())
