"""bench/trace_reduce.py: busy union, idle share, per-chip busy, top
operations and gaps named by host spans — on hand-built events, and on a
50 ms slice of a trace recorded on one chip, committed under
``tests/bench/fixtures``."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MS = 1_000_000  # ns


def _trace():
    """Window 0..100 ms; chip 0 runs 10-30 and 25-40 (overlapping) and
    70-80; chip 1 runs 10-50.  Host: a submit span 40-75, a sleep 0-12."""
    return {
        "devices": {
            0: [("conv", 10 * MS, 30 * MS), ("copy", 25 * MS, 40 * MS),
                ("conv", 70 * MS, 80 * MS)],
            1: [("conv", 10 * MS, 50 * MS)],
            2: [("conv", 0, 100 * MS)],  # not one of the cell's chips
        },
        "host": [(tr.BEGIN, 0, 0), (tr.END, 100 * MS, 100 * MS),
                 ("bench.submit", 40 * MS, 75 * MS), ("bench.sleep", 0, 12 * MS)],
    }


def test_busy_is_the_union_per_chip():
    r = tr.reduce(_trace(), chips=2)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s_per_chip"] == pytest.approx([0.040, 0.040])
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["chips"] == 2


def test_top_ops_sum_their_durations():
    r = tr.reduce(_trace(), chips=2)
    assert r["device_ops"] == [["conv", pytest.approx(0.070)], ["copy", pytest.approx(0.015)]]


def test_gaps_where_no_chip_runs_named_by_host_span():
    r = tr.reduce(_trace(), chips=2)
    # idle on both chips: 0-10, 50-70, 80-100
    assert r["idle_gaps"] == [["bench.submit", pytest.approx(0.020)],
                              ["host.other", pytest.approx(0.020)],
                              ["bench.sleep", pytest.approx(0.010)]]


def test_clipped_to_the_markers():
    t = _trace()
    t["host"][1] = (tr.END, 35 * MS, 35 * MS)
    r = tr.reduce(t, chips=1)
    assert r["window_s"] == pytest.approx(0.035)
    assert r["busy_s_per_chip"] == pytest.approx([0.025])


def test_missing_markers_is_an_error():
    t = _trace()
    t["host"] = t["host"][2:]
    with pytest.raises(ValueError, match="markers"):
        tr.reduce(t, chips=1)


def _fixture(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v] for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]], "expect": raw["expect"]}


def test_chip_fixture():
    t = _fixture("live_1chip.json.gz")
    r = tr.reduce(t, chips=len(t["devices"]))
    assert 0 < r["busy_s"] < r["window_s"]
    for busy in r["busy_s_per_chip"]:
        assert 0 < busy <= r["window_s"]
    # every chip's busy time is at most its summed op time, at least its longest op
    for d, busy in zip(sorted(t["devices"]), r["busy_s_per_chip"]):
        durs = [(e - s) / 1e9 for _, s, e in t["devices"][d]]
        assert max(durs) <= busy + 1e-12 and busy <= sum(durs) + 1e-12
    gaps = sum(g for _, g in r["idle_gaps"])
    assert gaps <= r["window_s"] - max(r["busy_s_per_chip"]) + 1e-9
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.submit", "bench.sleep", "bench.flush",
                                             "bench.result", "host.other"}
    for key, want in t["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
