"""bench/trace_program.py: the program's ``sr.*`` host spans and named
scopes read beside trace_reduce's numbers, which it leaves unchanged —
on hand-built events, on the committed slice of a trace of the program
before it had spans or scopes, and on a slice of a trace recorded on one
chip with both (``tests/bench/fixtures``)."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec, trace_program as tp, trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MS = 1_000_000  # ns


def _trace():
    """Window 0..100 ms; chip 0 runs an unscoped loop 10-30 around a
    feature op 12-28, an epilogue op 30-40 and an unscoped slice 70-80.
    Host: the benchmark's sleep 0-12 and submit 40-75; the program's
    device wait 12-30, completion 40-48, submit 50-75."""
    return {
        "devices": {0: [("while", 10 * MS, 30 * MS), ("conv", 12 * MS, 28 * MS),
                        ("copy", 30 * MS, 40 * MS), ("slice", 70 * MS, 80 * MS)],
                    1: [("conv", 0, 100 * MS)]},  # not one of the cell's chips
        "host": [(tr.BEGIN, 0, 0), (tr.END, 100 * MS, 100 * MS),
                 ("bench.sleep", 0, 12 * MS), ("bench.submit", 40 * MS, 75 * MS)],
        "program": [("sr.device_wait", 12 * MS, 30 * MS), ("sr.complete", 40 * MS, 48 * MS),
                    ("sr.submit", 50 * MS, 75 * MS)],
        "op_scopes": {"conv": "sr_features", "copy": "sr_epilogue"},
    }


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_execute_stack)/sr_features/vmap()/while/body/closed_call/and:", "sr_features"),
    ("jit(_execute_stack)/sr_epilogue/reshape", "sr_epilogue"),
    ("jit(_execute_stack)/sr_epilogue:", "sr_epilogue"),
    ("jit(_execute_stack)/convert_element_type", None),
    ("jit(f)/not_sr_epilogue/add", None),
])
def test_scope_of_reads_the_path(op_name, scope):
    assert tp.scope_of(op_name) == scope


def test_old_keys_are_trace_reduces():
    t = _trace()
    old = tr.reduce(t, chips=1)
    new = tp.reduce(t, chips=1)
    assert {k: new[k] for k in old} == old


def test_scopes_are_the_union_of_their_ops_in_the_window():
    r = tp.reduce(_trace(), chips=1)
    assert r["scopes"] == {"other": pytest.approx(0.030), "sr_epilogue": pytest.approx(0.010),
                           "sr_features": pytest.approx(0.016)}
    assert r["busy_s"] == pytest.approx(0.040)


def test_idle_gaps_named_by_program_spans():
    r = tp.reduce(_trace(), chips=1)
    # idle on chip 0: 0-10, 40-70, 80-100; the same gaps as idle_gaps
    assert [g for _, g in r["idle_gaps_program"]] == [g for _, g in r["idle_gaps"]]
    assert r["idle_gaps_program"] == [["sr.submit", pytest.approx(0.030)],
                                      ["host.other", pytest.approx(0.020)],
                                      ["host.other", pytest.approx(0.010)]]
    assert [n for n, _ in r["idle_gaps"]] == ["bench.submit", "host.other", "bench.sleep"]


def test_trace_without_program_marks():
    t = _trace()
    del t["program"], t["op_scopes"]
    r = tp.reduce(t, chips=1)
    assert r["scopes"] == {"other": pytest.approx(0.040)}
    assert {n for n, _ in r["idle_gaps_program"]} == {"host.other"}


def _fixture(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        raw = json.load(f)
    out = {"devices": {int(k): [tuple(e) for e in v] for k, v in raw["devices"].items()},
           "host": [tuple(e) for e in raw["host"]], "expect": raw["expect"]}
    if "program" in raw:
        out["program"] = [tuple(e) for e in raw["program"]]
        out["op_scopes"] = raw["op_scopes"]
    return out


def test_old_chip_fixture_keeps_every_key():
    """The slice recorded before the program had marks: trace_reduce's
    keys unchanged, every operation ``other``, every gap unnamed."""
    t = _fixture("live_1chip.json.gz")
    old = tr.reduce(t, chips=1)
    new = tp.reduce(t, chips=1)
    assert {k: new[k] for k in old} == old
    for key, want in t["expect"].items():
        assert new[key] == pytest.approx(want, rel=1e-9), key
    assert set(new["scopes"]) == {"other"}
    assert new["scopes"]["other"] >= new["busy_s"]
    assert {n for n, _ in new["idle_gaps_program"]} == {"host.other"}


def _record_cpu_trace(trace_dir):
    """A CPU profiler trace with the benchmark's markers and a program span."""
    import jax
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(trace_dir):
        with TraceAnnotation(tr.BEGIN):
            pass
        with TraceAnnotation("sr.submit"):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
        with TraceAnnotation(tr.END):
            pass


def test_load_for_finds_the_reduced_trace(tmp_path, monkeypatch):
    import tempfile

    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    for run in ("bench-a", "bench-b"):
        _record_cpu_trace(str(tmp_path / run / "trace"))
    want = tp.load(tr.find_xplane(str(tmp_path / "bench-a" / "trace")))
    assert [n for n, _, _ in want["program"]] == ["sr.submit"]
    reduced = tr.reduce(want, chips=1)
    found = tp.load_for(reduced)
    assert found is not None and found["host"] == want["host"]
    assert tp.load_for({**reduced, "window_s": reduced["window_s"] + 1e-9}) is None


def test_epilogue_share_reader(monkeypatch):
    read = spec.metric_reader("epilogue_device_share.live")

    class Ctx:
        trace, chips = None, 1

    assert read(Ctx) is None  # an untraced run
    Ctx.trace = {"window_s": 0.1}
    monkeypatch.setattr(tp, "load_for", lambda reduced: None)
    assert read(Ctx) is None  # no trace found
    monkeypatch.setattr(tp, "load_for", lambda reduced: _trace())
    assert read(Ctx) == pytest.approx(25.0)  # 10 ms of 40 ms busy
    unscoped = {**_trace(), "op_scopes": {}}
    monkeypatch.setattr(tp, "load_for", lambda reduced: unscoped)
    assert read(Ctx) is None  # a program without scopes: nothing to read


def test_chip_fixture_with_program_marks():
    """50 ms of a traced live run on one v5e: a 4-frame dispatch's input
    staging, its program and its completion.  ``expect`` holds the
    reduction's numbers at the cut (``make_program_fixture.py``)."""
    t = _fixture("live_1chip_program.json.gz")
    r = tp.reduce(t, chips=1)
    old = tr.reduce(t, chips=1)
    assert {k: r[k] for k in old} == old
    for key in ("window_s", "busy_s"):
        assert r[key] == pytest.approx(t["expect"][key], rel=1e-9), key
    assert r["scopes"] == pytest.approx(t["expect"]["scopes"], rel=1e-9)
    assert set(r["scopes"]) == {"other", "sr_epilogue", "sr_features"}
    for scope, seconds in r["scopes"].items():
        assert 0 < seconds <= r["busy_s"], scope
    assert [n for n, _ in r["idle_gaps_program"]] == [
        n for n, _ in t["expect"]["idle_gaps_program"]]
    assert [g for _, g in r["idle_gaps_program"]] == pytest.approx(
        [g for _, g in r["idle_gaps"]], rel=1e-12)
    # the longest idle stretches fall in the program's completion and
    # device wait, where the benchmark's own spans say only submit/sleep
    assert r["idle_gaps_program"][0][0] == "sr.complete"
    assert r["idle_gaps_program"][1][0] == "sr.device_wait"
    # the epilogue's relayout copies are scoped
    assert any(n.startswith("%copy.92 ") for n, s in t["op_scopes"].items()
               if s == "sr_epilogue")
