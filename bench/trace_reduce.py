"""From a profiler trace to device busy time, idle gaps and top operations.

A trace is reduced between two host markers the harness writes
(``bench.trace_begin``, ``bench.window_end``).  On each device plane
(``/device:TPU:<n>``) the operations are the events of its ``XLA Ops``
line; busy time is the union of their intervals, so overlapping events
count once.  An idle gap is a stretch of the window in which no chip of
the cell runs an operation; it is named by the benchmark's host span
(``bench.*``) that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
BEGIN, END = "bench.trace_begin", "bench.window_end"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> dict:
    """``{"devices": {id: [(name, start_ns, end_ns)]}, "host": [(name, start_ns, end_ns)]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], set()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                lines.add(line.name)
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host, "device_lines": sorted(lines)}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of the intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(trace: dict, chips: int) -> dict:
    """Busy and idle time of the cell's ``chips`` lowest-numbered devices
    between the two markers; ``None`` entries where the trace holds no
    device operation."""
    marks = {name: s for name, s, _ in trace["host"] if name in (BEGIN, END)}
    if BEGIN not in marks or END not in marks:
        raise ValueError(f"trace lacks the {BEGIN}/{END} markers")
    lo, hi = marks[BEGIN], marks[END]
    window_ns = hi - lo
    ids = sorted(trace["devices"])[:chips]
    busy, per_op = {}, defaultdict(float)
    for d in ids:
        ops = trace["devices"][d]
        busy[d] = sum(e - s for s, e in union([(s, e) for _, s, e in ops], lo, hi))
        for name, s, e in ops:
            per_op[name] += _overlap(s, e, lo, hi)
    all_busy = union([(s, e) for d in ids for _, s, e in trace["devices"][d]], lo, hi)
    gaps, cursor = [], lo
    for s, e in all_busy + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    spans = [(n, s, e) for n, s, e in trace["host"] if n not in (BEGIN, END)]
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        cover = defaultdict(float)
        for n, s, e in spans:
            cover[n] += _overlap(s, e, g0, g1)
        best = max(cover.items(), key=lambda kv: kv[1], default=("host.other", 0.0))
        named.append([best[0] if best[1] > 0 else "host.other", (g1 - g0) / 1e9])
    busy_s = [busy[d] / 1e9 for d in ids]
    return {
        "window_s": window_ns / 1e9,
        "chips": len(ids),
        "busy_s_per_chip": busy_s,
        "busy_s": sum(busy_s) / len(ids) if ids else 0.0,
        "ops": sum(len(trace["devices"][d]) for d in ids),
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": named,
    }
