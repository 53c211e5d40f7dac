"""Host spans and counters of the serving path (``repro.engine.spans``):
the recorder itself, the ``session.stats()`` keys the server fills from
it, the ``sr.*`` spans on a profiler trace's host plane, and the named
scopes on the executor's device operations (HLO metadata only: the HR
output is unchanged).  Fast tier, tiny tilted shapes."""

import glob
import re

import jax
import numpy as np
import pytest

from repro import engine
from repro.engine import executor as executor_mod
from repro.engine.server import SRServer
from repro.engine.session import SPAN_STATS
from repro.engine.spans import MAXLEN, Spans
from repro.models.abpn import ABPNConfig, init_abpn

LAYERS = init_abpn(jax.random.PRNGKey(2), ABPNConfig())
LR = (12, 16, 3)
CLIP = np.asarray(jax.random.uniform(jax.random.PRNGKey(21), (6, *LR)))
SPAN_NAMES = ("sr.submit", "sr.assemble", "sr.launch", "sr.device_wait", "sr.complete")


def served_session(frames=4):
    session = engine.SRSession(LAYERS, backend="tilted", max_bucket=2)
    server = SRServer({"abpn": session})
    futures = [server.submit(CLIP[i]) for i in range(frames)]
    for f in futures:
        f.result()
    return server, session


# ----------------------------------------------------------------------
# The recorder
# ----------------------------------------------------------------------
def test_span_times_the_block_and_records_milliseconds():
    s = Spans()
    with s.span("work") as sp:
        sum(range(1000))
    assert sp.t1 >= sp.t0 and sp.ms == pytest.approx((sp.t1 - sp.t0) * 1e3)
    assert s.values("work") == (sp.ms,)
    s.record("wait", 2.5)
    assert s.values("wait") == (2.5,)


def test_span_that_raises_records_nothing():
    s = Spans()
    with pytest.raises(ValueError):
        with s.span("launch"):
            raise ValueError("failed launch")
    assert s.values("launch") == ()


def test_series_stay_bounded_past_maxlen():
    s = Spans(maxlen=8)
    for i in range(20):
        s.record("queue_wait", float(i))
        with s.span("submit"):
            pass
    assert s.values("queue_wait") == tuple(float(i) for i in range(12, 20))
    assert len(s.values("submit")) == 8
    assert Spans().series("x").maxlen == MAXLEN


def test_reset_clears_every_series():
    s = Spans()
    s.record("a", 1.0)
    with s.span("b"):
        pass
    s.reset()
    assert s.values("a") == () and s.values("b") == ()


# ----------------------------------------------------------------------
# session.stats() from the server's spans and counters
# ----------------------------------------------------------------------
def test_server_run_fills_every_span_stat_and_reset_empties_them():
    _, session = served_session()
    stats = session.stats()
    for name in SPAN_STATS:
        assert stats[f"{name}_p50_ms"] > 0, name
        assert len(session.spans.values(name)) > 0, name
    assert len(session.spans.values("submit")) == 4
    # one queue wait per request (each request's first frame), one
    # assemble / device_ready / complete per dispatch
    assert len(session.spans.values("queue_wait")) == 4
    for name in ("assemble", "device_ready", "complete", "launch", "latency"):
        assert len(session.spans.values(name)) == stats["batches"], name
    session.reset_stats()
    stats = session.stats()
    for name in SPAN_STATS:
        assert stats[f"{name}_p50_ms"] == 0.0
        assert session.spans.values(name) == ()
    assert stats["batches"] == 0 and stats["dispatch_mean_ms"] == 0.0


def test_dispatch_stats_read_the_launch_span():
    _, session = served_session()
    launch = np.asarray(session.spans.values("launch"))
    latency = np.asarray(session.spans.values("latency"))
    stats = session.stats()
    assert stats["dispatch_mean_ms"] == pytest.approx(launch.mean(), rel=1e-12)
    assert stats["dispatch_p50_ms"] == pytest.approx(np.percentile(launch, 50), rel=1e-12)
    assert stats["mean_ms"] == pytest.approx(latency.mean(), rel=1e-12)
    # a dispatch's latency runs from its launch to its completion: it
    # holds the launch and the device wait after it
    assert (latency >= launch).all()
    ready = np.asarray(session.spans.values("device_ready"))
    assert (latency + 1e-9 >= launch + ready).all()


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    server, session = served_session(frames=1)  # compiled before the trace
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            server.submit(CLIP[i]).result()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events if e.name.startswith("sr."))
    assert set(SPAN_NAMES) <= names


# ----------------------------------------------------------------------
# Device scopes: metadata only
# ----------------------------------------------------------------------
def test_cached_executor_hlo_carries_the_scopes():
    session = engine.SRSession(LAYERS, backend="tilted", max_bucket=2)
    session.upscale(CLIP[:2])
    (entry,) = session._cache.entries()
    stack = next(iter(session._stacks.values())).stack
    arts = executor_mod.executor_artifacts(entry.plan, stack, entry.bucket)
    for scope in ("sr_features", "sr_epilogue"):
        assert re.search(rf'op_name="[^"]*/{scope}/', arts["hlo"]), scope


def test_pallas_call_is_named():
    from repro.kernels import ops

    x = jax.ShapeDtypeStruct((1, *LR), np.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda f: ops.tilted_fused_frames(f, LAYERS, band_rows=12, interpret=True))(x))
    assert re.search(r"name=tilted_fusion\b", jaxpr)


@pytest.mark.parametrize("backend", ["tilted", "kernel"])
def test_served_output_bit_identical_to_plain_execute(backend):
    session = engine.SRSession(LAYERS, backend=backend, max_bucket=2)
    server = SRServer({"abpn": session})
    served = np.asarray(server.submit(CLIP[:2]).result())
    plan = session.plan_for(LR)
    plain = np.asarray(executor_mod._execute(plan, list(LAYERS), CLIP[:2]))
    np.testing.assert_array_equal(served, plain)
