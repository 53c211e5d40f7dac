"""The program's own marks in a profiler trace, beside ``trace_reduce``'s.

The serving path times its host work as ``sr.*`` spans
(``repro.engine.spans``) and labels its device work with named scopes
(``sr_features``, ``sr_epilogue``), which XLA keeps in each operation's
``op_name`` metadata.  ``load`` is ``trace_reduce.load`` plus
``program`` (the ``sr.*`` host events) and ``op_scopes`` (``{op name:
scope}``, read from the framework-name stat ``SCOPE_STAT`` of each device
operation's event metadata, which ``ProfileData`` does not expose: the
``.xplane.pb`` is read for it as protobuf wire format).  ``reduce`` is
``trace_reduce.reduce`` plus ``scopes`` (per scope, the seconds its
operations cover in the slice, their union averaged over the cell's
chips as ``busy_s`` is; ``other`` for operations outside every scope,
among them control flow such as the tilted scan's ``while``, whose
metadata carries no scope and whose time its body's operations cover
again) and ``idle_gaps_program`` (the same gaps as ``idle_gaps``,
each named by the ``sr.*`` span that covers most of it, else
``host.other``).  A program without these marks gives an empty
``program`` and ``op_scopes``: every operation ``other``, every gap
``host.other``.

The harness keeps the trace in its run's temporary directory and hands a
metric reader only ``trace_reduce.reduce``'s numbers; ``load_for`` finds
that trace again: the one whose markers span exactly the reduced window.
"""

from __future__ import annotations

import glob
import os
import tempfile
from collections import defaultdict
from typing import Optional

from bench import trace_reduce as tr

PROGRAM_PREFIX = "sr."
SCOPES = ("sr_features", "sr_epilogue")
SCOPE_STAT = "tf_op"  # the framework op name: named_scope's op_name path
OTHER = "other"


def scope_of(op_name: str) -> Optional[str]:
    """The named scope in an ``op_name`` path (``jit(f)/sr_epilogue/add:``),
    or ``None``."""
    parts = [p.rstrip(":") for p in op_name.split("/")]
    return next((s for s in SCOPES if s in parts), None)


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(path: str) -> dict:
    """``{op name: scope}`` over the device planes of an ``.xplane.pb``.

    XSpace ``planes`` (1); XPlane ``name`` (2), ``event_metadata`` (4)
    and ``stat_metadata`` (5), maps of ``key`` (1) to ``value`` (2);
    XEventMetadata ``name`` (2), ``display_name`` (4), ``stats`` (5);
    XStatMetadata ``name`` (2); XStat ``metadata_id`` (1) with the string
    in ``str_value`` (5) or, interned, as the name of the stat metadata
    ``ref_value`` (7) points to."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for field, v in _fields(plane):
            if field == 2:
                name = _text(v)
            elif field == 4:
                metas.append(v)
            elif field == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = _text(dict(_fields(entry.get(2, b""))).get(2, b""))
        if not tr.DEVICE_PLANE.match(name):
            continue
        for entry in metas:
            meta = dict(_fields(entry)).get(2, b"")
            names, scope = [], None
            for field, v in _fields(meta):
                if field in (2, 4):
                    names.append(_text(v))
                elif field == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1, 0)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = scope_of(_text(stat[5]))
                    elif 7 in stat:
                        scope = scope_of(stat_names.get(stat[7], ""))
            if scope is not None:
                out.update((n, scope) for n in names if n)
    return out


def load(path: str) -> dict:
    """``trace_reduce.load``'s keys plus ``program`` and ``op_scopes``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, program, lines = {}, [], [], set()
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                lines.add(line.name)
                if line.name == tr.OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(tr.HOST_PREFIX):
                        host.append(span)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append(span)
    return {"devices": devices, "host": host, "device_lines": sorted(lines),
            "program": program, "op_scopes": op_scopes(path)}


def _window(trace: dict):
    marks = {name: s for name, s, _ in trace["host"] if name in (tr.BEGIN, tr.END)}
    if tr.BEGIN not in marks or tr.END not in marks:
        raise ValueError(f"trace lacks the {tr.BEGIN}/{tr.END} markers")
    return marks[tr.BEGIN], marks[tr.END]


def reduce(trace: dict, chips: int) -> dict:
    """``trace_reduce.reduce`` plus ``scopes`` and ``idle_gaps_program``."""
    out = tr.reduce(trace, chips)
    lo, hi = _window(trace)
    ids = sorted(trace["devices"])[:chips]
    op_scopes = trace.get("op_scopes", {})
    scopes = defaultdict(float)
    for d in ids:
        by_scope = defaultdict(list)
        for name, s, e in trace["devices"][d]:
            by_scope[op_scopes.get(name, OTHER)].append((s, e))
        for scope, intervals in by_scope.items():
            scopes[scope] += sum(e - s for s, e in tr.union(intervals, lo, hi))
    out["scopes"] = {k: v / 1e9 / len(ids) for k, v in sorted(scopes.items()) if v > 0}
    out["idle_gaps_program"] = _name_gaps(trace, ids, lo, hi, trace.get("program", []))
    return out


def _name_gaps(trace, ids, lo, hi, spans) -> list:
    """``trace_reduce.reduce``'s ten longest idle gaps, each named by the
    span in ``spans`` that covers most of it."""
    busy = tr.union([(s, e) for d in ids for _, s, e in trace["devices"][d]], lo, hi)
    gaps, cursor = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:tr.TOP]:
        cover = defaultdict(float)
        for n, s, e in spans:
            cover[n] += tr._overlap(s, e, g0, g1)
        best = max(cover.items(), key=lambda kv: kv[1], default=("host.other", 0.0))
        named.append([best[0] if best[1] > 0 else "host.other", (g1 - g0) / 1e9])
    return named


def load_for(reduced: dict) -> Optional[dict]:
    """The trace (``load``'s dict) a harness run just reduced to
    ``reduced``: under the temporary directory, newest first, the first
    whose markers span exactly ``reduced["window_s"]``; ``None`` if none."""
    pattern = os.path.join(tempfile.gettempdir(), "bench-*", "trace", "**", "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                       reverse=True):
        trace = load(path)
        try:
            lo, hi = _window(trace)
        except ValueError:
            continue
        if (hi - lo) / 1e9 == reduced["window_s"]:
            return trace
    return None
