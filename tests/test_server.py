"""SRServer front door: submit/future parity, cross-request micro-batching,
priority, backpressure, streaming, multi-model routing, input validation,
and PlanCache + PreparedStack refcounting under interleaved traffic.
All fast tier (tiny tilted shapes).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.engine.scheduler import MicroBatchScheduler, QueueFullError
from repro.engine.server import SRFuture, SRServer
from repro.models.abpn import ABPNConfig, init_abpn

CFG = ABPNConfig()
LAYERS = init_abpn(jax.random.PRNGKey(2), CFG)
LR = (12, 16, 3)
CLIP = jax.random.uniform(jax.random.PRNGKey(21), (8, *LR))
ORACLE = None  # filled lazily (module import must stay cheap)


def oracle(frames):
    global ORACLE
    if ORACLE is None:
        plan = engine.make_plan(LAYERS, LR, band_rows=12, backend="tilted")
        ORACLE = np.asarray(engine.run(plan, LAYERS, CLIP))
    n = frames.shape[0]
    for i in range(CLIP.shape[0] - n + 1):
        if np.array_equal(np.asarray(frames), np.asarray(CLIP[i:i + n])):
            return ORACLE[i:i + n]
    raise AssertionError("frames are not a contiguous CLIP slice")


def make_session(**kw):
    kw.setdefault("backend", "tilted")
    return engine.SRSession(LAYERS, **kw)


def make_server(*, session_kw=None, **server_kw):
    session = make_session(**(session_kw or {}))
    return SRServer({"abpn": session}, **server_kw), session


# ----------------------------------------------------------------------
# Parity: submit == upscale == the unbatched engine oracle
# ----------------------------------------------------------------------
def test_submit_parity_with_upscale_and_oracle():
    server, session = make_server()
    hr = server.submit(CLIP[:3]).result()
    np.testing.assert_array_equal(np.asarray(hr), oracle(CLIP[:3]))
    # upscale IS submit().result() — bit-exact on a fresh same-weights session
    np.testing.assert_array_equal(
        np.asarray(make_session().upscale(CLIP[:3])), np.asarray(hr))
    # rank 3 and rank 5 round-trip through the future path
    single = server.submit(CLIP[0]).result()
    assert single.shape == (36, 48, 3)
    np.testing.assert_array_equal(np.asarray(single), oracle(CLIP[:1])[0])
    nested = server.submit(CLIP[:4].reshape(2, 2, *LR)).result()
    assert nested.shape == (2, 2, 36, 48, 3)
    np.testing.assert_array_equal(
        np.asarray(nested).reshape(4, 36, 48, 3), oracle(CLIP[:4]))


def test_submit_numpy_input_matches_device_input():
    server, _ = make_server(session_kw={"max_bucket": 4})
    out_np = server.submit(np.asarray(CLIP[:6])).result()
    np.testing.assert_array_equal(np.asarray(out_np), oracle(CLIP[:6]))


def test_upscale_uses_embedded_server_lazily():
    session = make_session()
    assert session._server is None
    out = session.upscale(CLIP[:2])
    assert session._server is not None
    np.testing.assert_array_equal(np.asarray(out), oracle(CLIP[:2]))
    assert session._server.scheduler_stats()["dispatches"] == 1
    assert session.stats()["frames"] == 2


# ----------------------------------------------------------------------
# Coalescing (the acceptance scenario)
# ----------------------------------------------------------------------
def test_two_half_bucket_requests_coalesce_into_one_full_dispatch():
    """Two concurrent same-plan requests of bucket/2 frames are served as
    ONE coalesced bucket-sized dispatch: 1 dispatch, fill ratio 1.0 —
    real frames fill the power-of-two bucket instead of padding."""
    bucket = 4
    server, session = make_server(session_kw={"max_bucket": bucket})
    f1 = server.submit(CLIP[:2])          # bucket/2 frames
    f2 = server.submit(CLIP[2:4])         # bucket/2 frames, same plan/dtype
    assert not f1.done() and not f2.done()  # queued, not yet dispatched
    r1 = f1.result()                      # drives the drain
    s = server.scheduler_stats()
    assert s["dispatches"] == 1
    assert s["coalesced_dispatches"] == 1
    assert s["mean_fill_ratio"] == 1.0
    assert s["frames_dispatched"] == 4 and s["padded_frames"] == 0
    assert f2.done()  # completed by the same dispatch
    np.testing.assert_array_equal(np.asarray(r1), oracle(CLIP[:2]))
    np.testing.assert_array_equal(np.asarray(f2.result()), oracle(CLIP[2:4]))
    d = s["recent_dispatches"][0]
    assert d["requests"] == 2 and d["bucket"] == bucket and d["fill"] == 1.0
    # the session compiled exactly one executor, for the full bucket
    assert [e["bucket"] for e in session.cache_stats()["entries"]] == [bucket]


def test_solo_request_pads_its_bucket():
    """The contrast case: a lone 3-frame request pads a 4-bucket (fill
    0.75) — the padding coalescing exists to eliminate."""
    server, _ = make_server()
    server.submit(CLIP[:3]).result()
    s = server.scheduler_stats()
    assert s["dispatches"] == 1 and s["coalesced_dispatches"] == 0
    assert s["mean_fill_ratio"] == pytest.approx(0.75)
    assert s["padded_frames"] == 1


def test_odd_requests_fill_one_bucket_with_real_frames():
    """1+3 concurrent frames -> one full 4-bucket: zero padding, where
    solo serving would have dispatched twice with a padded bucket."""
    server, _ = make_server(session_kw={"max_bucket": 4})
    f1 = server.submit(CLIP[0])           # 1 frame (rank 3)
    f2 = server.submit(CLIP[1:4])         # 3 frames
    server.flush()
    s = server.scheduler_stats()
    assert s["dispatches"] == 1 and s["mean_fill_ratio"] == 1.0
    np.testing.assert_array_equal(np.asarray(f1.result()), oracle(CLIP[:1])[0])
    np.testing.assert_array_equal(np.asarray(f2.result()), oracle(CLIP[1:4]))


def test_large_request_carries_its_bucket_and_tail_coalesces():
    """A request bigger than the max bucket spans dispatches at ONE pinned
    bucket (no tail-driven second compile), and a later request's frames
    top up the tail dispatch."""
    server, session = make_server(session_kw={"max_bucket": 4})
    f1 = server.submit(CLIP[:5])          # 4 + 1-frame tail
    f2 = server.submit(CLIP[5:8])         # 3 frames join the tail dispatch
    server.flush()
    s = server.scheduler_stats()
    assert s["dispatches"] == 2 and s["mean_fill_ratio"] == 1.0
    assert [e["bucket"] for e in session.cache_stats()["entries"]] == [4]
    np.testing.assert_array_equal(np.asarray(f1.result()), oracle(CLIP[:5]))
    np.testing.assert_array_equal(np.asarray(f2.result()), oracle(CLIP[5:8]))


def test_priority_picks_the_next_dispatch():
    """Across coalescing keys, the highest-priority pending request's key
    dispatches first (FIFO within a priority level)."""
    session = make_session()
    server = SRServer({"abpn": session})
    server.submit(jnp.ones((1, *LR)), priority=0)
    server.submit(jnp.ones((1, 24, 16, 3)), priority=5)  # other key
    server.flush()
    log = server.scheduler_stats()["recent_dispatches"]
    assert [d["lr_shape"] for d in log] == [[24, 16, 3], [12, 16, 3]]
    assert log[0]["priority"] == 5


# ----------------------------------------------------------------------
# Backpressure
# ----------------------------------------------------------------------
def test_backpressure_reject_policy():
    server, _ = make_server(max_inflight_frames=2, admission="reject")
    f1 = server.submit(CLIP[:2])
    with pytest.raises(QueueFullError, match="queue full"):
        server.submit(CLIP[2:3])
    assert server.scheduler_stats()["rejected"] == 1
    f1.result()  # drains the queue — space again
    np.testing.assert_array_equal(
        np.asarray(server.submit(CLIP[2:3]).result()), oracle(CLIP[2:3]))
    with pytest.raises(ValueError, match="can never fit"):
        server.submit(CLIP[:3])  # larger than the bound itself


def test_backpressure_block_policy_drains_to_admit():
    server, _ = make_server(max_inflight_frames=2, admission="block")
    futs = [server.submit(CLIP[i:i + 2]) for i in range(0, 8, 2)]
    server.flush()
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(
            np.asarray(f.result()), oracle(CLIP[2 * i:2 * i + 2]))
    s = server.scheduler_stats()
    assert s["rejected"] == 0 and s["pending_frames"] == 0
    assert s["peak_pending_frames"] <= 2


# ----------------------------------------------------------------------
# Multi-model routing
# ----------------------------------------------------------------------
def test_multi_model_routing_never_coalesces_across_models():
    sa, sb = make_session(), make_session(precision="int8")
    server = SRServer({"a": sa, "b": sb})
    fa = server.submit(CLIP[:2], model="a")
    fb = server.submit(CLIP[2:4], model="b")
    server.flush()
    s = server.scheduler_stats()
    assert s["dispatches"] == 2 and s["coalesced_dispatches"] == 0
    np.testing.assert_array_equal(np.asarray(fa.result()), oracle(CLIP[:2]))
    assert fb.result().shape == (2, 36, 48, 3)
    assert sa.stats()["frames"] == 2 and sb.stats()["frames"] == 2
    with pytest.raises(ValueError, match="unknown model"):
        server.submit(CLIP[:1], model="c")
    assert server.models == ("a", "b") and server.session("b") is sb
    # default model is the first hosted session
    assert server.session() is sa


def test_server_open_resolves_registry():
    server = SRServer.open("abpn_x3", backend="tilted", seed=3)
    assert server.models == ("abpn_x3",)
    out = server.submit(jnp.ones((1, *LR))).result()
    assert out.shape == (1, 36, 48, 3)
    with pytest.raises(ValueError, match="unknown SR model"):
        SRServer.open("espcn_x4")


def test_server_open_ignores_tuning_db(tmp_path):
    path = tmp_path / "tuning.json"
    with SRServer.open("abpn_x3", layers=LAYERS, tuning_db=str(path)) as server:
        out = server.submit(np.zeros((2, *LR), np.float32)).result()
    assert out.shape == (2, 36, 48, 3)
    assert not path.exists() and list(tmp_path.iterdir()) == []


# ----------------------------------------------------------------------
# Streaming
# ----------------------------------------------------------------------
def test_stream_yields_in_order_and_coalesces_lookahead():
    server, _ = make_server(session_kw={"max_bucket": 4})

    async def run():
        outs = []
        async for hr in server.stream(list(CLIP[:4]), lookahead=4):
            outs.append(np.asarray(hr))
        return outs

    outs = asyncio.run(run())
    assert len(outs) == 4
    np.testing.assert_array_equal(np.stack(outs), oracle(CLIP[:4]))
    s = server.scheduler_stats()
    # the lookahead window coalesced the four single frames into one bucket
    assert s["dispatches"] == 1 and s["mean_fill_ratio"] == 1.0


def test_two_concurrent_streams_share_the_server():
    server, _ = make_server(session_kw={"max_bucket": 4})

    async def one(clip):
        outs = []
        async for hr in server.stream(list(clip), lookahead=2):
            outs.append(np.asarray(hr))
        return outs

    async def both():
        return await asyncio.gather(one(CLIP[:3]), one(CLIP[3:6]))

    a, b = asyncio.run(both())
    np.testing.assert_array_equal(np.stack(a), oracle(CLIP[:3]))
    np.testing.assert_array_equal(np.stack(b), oracle(CLIP[3:6]))
    assert server.scheduler_stats()["frames_dispatched"] == 6


# ----------------------------------------------------------------------
# SRFuture API + failure propagation
# ----------------------------------------------------------------------
def test_future_api_done_callback_and_repeat_result():
    server, _ = make_server()
    fired = []
    fut = server.submit(CLIP[:1])
    fut.add_done_callback(lambda f: fired.append(f.done()))
    out = fut.result()
    assert fired == [True] and fut.done() and fut.exception() is None
    np.testing.assert_array_equal(np.asarray(fut.result()), np.asarray(out))
    # a callback added after completion fires immediately
    fut.add_done_callback(lambda f: fired.append("late"))
    assert fired == [True, "late"]


def test_done_callback_may_submit_follow_up_work():
    """Callbacks run OUTSIDE the server lock: chaining the next request
    from a done-callback (the natural use of the API) must not deadlock
    the draining thread."""
    server, _ = make_server()
    chained = []
    fut = server.submit(CLIP[:1])
    fut.add_done_callback(
        lambda f: chained.append(server.submit(CLIP[1:2])))
    out = fut.result()
    np.testing.assert_array_equal(np.asarray(out), oracle(CLIP[:1]))
    assert len(chained) == 1
    np.testing.assert_array_equal(
        np.asarray(chained[0].result()), oracle(CLIP[1:2]))


def test_dispatch_failure_sets_future_exception(monkeypatch):
    server, session = make_server()
    ok = server.submit(CLIP[:1]).result()  # compile the happy path first

    def boom(plan, bucket, dtype):
        raise RuntimeError("executor exploded")

    monkeypatch.setattr(session, "executor_for", boom)
    fut = server.submit(CLIP[1:3])
    with pytest.raises(RuntimeError, match="executor exploded"):
        fut.result()
    assert isinstance(fut.exception(), RuntimeError)
    assert server.scheduler_stats()["pending_frames"] == 0  # remainder dropped
    monkeypatch.undo()
    # the server keeps serving after a failed dispatch
    np.testing.assert_array_equal(
        np.asarray(server.submit(CLIP[:1]).result()), np.asarray(ok))


def test_kernel_compile_failure_fails_request_without_backend_swap(monkeypatch):
    """A kernel the compiler refuses fails the request; the session never
    serves it through another backend instead."""
    from repro.kernels import tilted_fusion

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(tilted_fusion, "tilted_fusion_call", refuse)
    server, session = make_server(session_kw={"backend": "kernel"})
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        server.submit(CLIP[:2]).result()
    assert session.cache_stats()["entries"] == []  # nothing compiled instead


def test_empty_request_resolves_immediately():
    server, _ = make_server()
    fut = server.submit(jnp.zeros((0, *LR)))
    assert fut.done()
    assert fut.result().shape == (0, 36, 48, 3)
    s = server.scheduler_stats()
    assert s["dispatches"] == 0 and s["submitted_requests"] == 1


def test_closed_server_rejects_submits():
    server, _ = make_server()
    fut = server.submit(CLIP[:1])
    with server:
        pass  # __exit__ flushes + closes
    assert fut.done()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(CLIP[:1])


# ----------------------------------------------------------------------
# Input validation (satellite: clear errors at the front door)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bad", ["nope", None, object()])
def test_submit_rejects_non_array_input(bad):
    session = make_session()
    with pytest.raises(ValueError, match=r"\(\.\.\., H, W, C\)"):
        session.submit(bad)
    with pytest.raises(ValueError, match=r"\(\.\.\., H, W, C\)"):
        session.upscale(bad)


def test_submit_rejects_wrong_channel_count_and_rank():
    session = make_session()
    with pytest.raises(ValueError, match="channels.*expects C=3"):
        session.upscale(jnp.ones((2, 12, 16, 4)))
    with pytest.raises(ValueError, match=r"\(H, W, C\)"):
        session.upscale(jnp.ones((12, 16)))  # rank 2
    with pytest.raises(ValueError, match=r"\(H, W, C\)"):
        session.upscale(jnp.ones((1, 1, 2, 12, 16, 3)))  # rank 6
    with pytest.raises(ValueError, match="numeric frames"):
        session.upscale(np.array([["a", "b"]], dtype=object))
    # nested numeric lists still serve (converted on the host path)
    out = session.upscale(np.zeros((12, 16, 3)).tolist())
    assert out.shape == (36, 48, 3)


# ----------------------------------------------------------------------
# Constructor validation (satellite: fail at construction, clearly)
# ----------------------------------------------------------------------
def test_session_constructor_validation():
    with pytest.raises(ValueError, match="cache_capacity=0"):
        engine.SRSession(LAYERS, cache_capacity=0)
    with pytest.raises(ValueError, match="pipeline_depth=0"):
        engine.SRSession(LAYERS, pipeline_depth=0)
    with pytest.raises(ValueError, match="max_bucket=0"):
        engine.SRSession(LAYERS, max_bucket=0)


def test_server_constructor_validation():
    session = make_session()
    with pytest.raises(ValueError, match="at least one session"):
        SRServer({})
    with pytest.raises(ValueError, match="max_inflight_frames=0"):
        SRServer({"a": session}, max_inflight_frames=0)
    with pytest.raises(ValueError, match="admission"):
        SRServer({"a": session}, admission="drop")
    with pytest.raises(ValueError, match="default_model"):
        SRServer({"a": session}, default_model="b")
    with pytest.raises(ValueError, match="must map to an SRSession"):
        SRServer({"a": object()})
    # a bare session is hosted under its model name
    named = SRServer(engine.SRSession.open("abpn_x3", layers=LAYERS))
    assert named.models == ("abpn_x3",)


# ----------------------------------------------------------------------
# PlanCache + PreparedStack refcounting under interleaved traffic
# (satellite: evictions hit live and dead stacks; no weight leak)
# ----------------------------------------------------------------------
def test_refcounting_under_interleaved_multi_model_traffic():
    """Two models alternating resolutions through capacity-1 caches: every
    miss evicts the other resolution's entry while its shared stack is
    still live; refs always equal live entries, and close() releases
    everything — no weight leak."""
    sa = make_session(precision="int8", cache_capacity=1)
    sb = make_session(precision="fp32", cache_capacity=1)
    server = SRServer({"a": sa, "b": sb})
    res = [(1, *LR), (1, 24, 16, 3)]
    for rep in range(2):
        for shape in res:
            for model in ("a", "b"):
                server.submit(jnp.ones(shape), model=model).result()
    for session, skey in ((sa, ("int8", "tilted")), (sb, ("fp32", "tilted"))):
        s = session.cache_stats()
        # 2 resolutions x 2 reps, capacity 1: every serve re-misses
        assert s["misses"] == 4 and s["hits"] == 0 and s["evictions"] == 3
        assert s["size"] == 1
        # the evictions hit a LIVE stack each time: the shared PreparedStack
        # survived (refcount moved 2 -> 1), never leaked a second copy
        assert len(session._stacks) == 1
        assert session._stacks[skey].refs == 1
        assert s["stacks"][0]["refs"] == 1
    sa.clear_cache()
    sb.clear_cache()
    assert sa._stacks == {} and sb._stacks == {}  # dead stacks dropped


def test_scheduler_counters_and_drop_bookkeeping():
    sched = MicroBatchScheduler()
    assert not sched.has_pending()
    s = sched.stats()
    assert s["dispatches"] == 0 and s["mean_fill_ratio"] == 0.0
    sched.note_rejected()
    assert sched.stats()["rejected"] == 1


def test_dropping_partial_request_releases_carry_bucket(monkeypatch):
    """A failed partially-served request must unpin its carry bucket:
    the next request on the key dispatches at its own natural bucket, not
    the dead request's."""
    server, session = make_server(session_kw={"max_bucket": 4})
    big = server.submit(CLIP[:6])  # 4 + 2-frame tail at carry bucket 4
    real_fn = session.executor_for
    calls = {"n": 0}

    def fail_second(plan, bucket, dtype):
        calls["n"] += 1
        if calls["n"] == 2:  # the tail dispatch
            raise RuntimeError("tail exploded")
        return real_fn(plan, bucket, dtype)

    monkeypatch.setattr(session, "executor_for", fail_second)
    with pytest.raises(RuntimeError, match="tail exploded"):
        big.result()
    monkeypatch.undo()
    fut = server.submit(CLIP[6:7])  # 1 frame — natural bucket 1, not 4
    np.testing.assert_array_equal(np.asarray(fut.result()), oracle(CLIP[6:7]))
    assert server.scheduler_stats()["recent_dispatches"][-1]["bucket"] == 1


def test_hosting_an_already_served_session_is_rejected():
    """A session that already has a front door (embedded or another host)
    cannot be hosted again — two schedulers/locks over one session's
    staging buffer and caches would race."""
    session = make_session()
    session.upscale(CLIP[:1])  # creates the embedded server
    with pytest.raises(ValueError, match="already served by another SRServer"):
        SRServer({"m": session})
    hosted = make_session()
    SRServer({"m": hosted})
    with pytest.raises(ValueError, match="already served by another SRServer"):
        SRServer({"again": hosted})
    # the same session under two names in ONE server is fine (aliasing)
    twin = make_session()
    server = SRServer({"x": twin, "y": twin})
    assert twin._server is server


def test_future_exception_returns_stored_timeout_error(monkeypatch):
    """A dispatch failure that IS a TimeoutError must be returned by
    exception(), not re-raised as if the wait timed out."""
    server, session = make_server()

    def slow(plan, bucket, dtype):
        raise TimeoutError("device timed out")

    monkeypatch.setattr(session, "executor_for", slow)
    fut = server.submit(CLIP[:1])
    exc = fut.exception()
    assert isinstance(exc, TimeoutError) and "device timed out" in str(exc)


def test_hosted_session_upscale_routes_through_hosting_server():
    """upscale/submit on a hosted session must use the HOSTING server (one
    scheduler, one lock over the session), not spawn a second embedded
    front door over the same mutable state."""
    sa, sb = make_session(), make_session()
    server = SRServer({"a": sa, "b": sb})
    assert sa._server is server and sb._server is server
    out = sb.upscale(CLIP[:2])
    np.testing.assert_array_equal(np.asarray(out), oracle(CLIP[:2]))
    s = server.scheduler_stats()
    assert s["submitted_requests"] == 1 and s["dispatches"] == 1
    assert s["recent_dispatches"][0]["model"] == "b"
    # a foreign session is rejected by identity-addressed submit
    with pytest.raises(ValueError, match="not hosted"):
        server.submit_for(make_session(), CLIP[:1])


def test_concurrent_submit_threads_coalesce_and_serve_correctly():
    """Many threads submitting + waiting concurrently: every result is
    bit-exact and the scheduler's frame accounting balances (the device
    wait releases the lock, so admission proceeds during drains)."""
    import threading

    server, _ = make_server(session_kw={"max_bucket": 8})
    results = {}

    def client(i):
        results[i] = np.asarray(server.submit(CLIP[i:i + 2]).result())

    threads = [threading.Thread(target=client, args=(i,)) for i in range(0, 6, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in (0, 2, 4):
        np.testing.assert_array_equal(results[i], oracle(CLIP[i:i + 2]))
    s = server.scheduler_stats()
    assert s["frames_dispatched"] == 6 and s["pending_frames"] == 0
    assert s["inflight_dispatches"] == 0 and s["dispatches"] <= 3
