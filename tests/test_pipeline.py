"""Pipelined serving: prepared-weight hoisting, async-vs-sync parity,
dispatch/complete stats, ragged-tail staging, stack lifecycle.

The acceptance checks for the pipelined serving path:

* weight prep (``prepare_layers`` / the kernel's pack) no longer executes
  inside the per-batch jitted call (jaxpr + prepare-call-count tests);
* async (``pipeline_depth`` >= 2) output is BIT-EXACT against sync
  (``pipeline_depth=1``) for every backend and precision;
* dispatch latency is recorded separately from complete latency, and a
  synchronous caller sees identical values;
* ragged tails reuse one staging buffer and never trigger a shape-driven
  recompile;
* evicting a cache entry releases its reference on the device-resident
  ``PreparedStack`` (no weight leak).
"""

import gc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare env: deterministic fallback sampler
    from _hypothesis_compat import given, settings, strategies as st

from repro import engine
from repro.engine import executor as executor_mod
from repro.models.abpn import ABPNConfig, init_abpn

CFG = ABPNConfig()
LAYERS = init_abpn(jax.random.PRNGKey(2), CFG)
CLIP = jax.random.uniform(jax.random.PRNGKey(11), (7, 12, 16, 3))
LR = (12, 16, 3)


def small_session(**kw):
    kw.setdefault("backend", "tilted")
    kw.setdefault("max_bucket", 2)  # 7-frame clip -> 4 chunks (ragged tail)
    return engine.SRSession(LAYERS, **kw)


# ----------------------------------------------------------------------
# Weight prep is hoisted out of the per-batch jitted call
# ----------------------------------------------------------------------
def test_weight_prep_absent_from_jitted_program():
    """The serving executor's traced program contains NO quantisation ops:
    the int8 round-trip (jnp.round/clip) runs once in prepare_stack, so the
    per-batch jaxpr is pure conv datapath.  Enforced through the SAME
    ``program_audit`` pass CI runs (not a bespoke token match); the legacy
    self-contained path keeps tracing the round-trip in — the control that
    the audit rule means something."""
    from repro.analysis import program_audit

    plan = engine.make_plan(LAYERS, LR, band_rows=12, backend="tilted",
                            precision="int8")
    stack = engine.prepare_stack(plan, LAYERS)
    arts = executor_mod.executor_artifacts(
        plan, stack, 2, compiled=False
    )
    assert program_audit.audit_jaxpr(arts["jaxpr"], precision="int8") == []
    dummy = jnp.zeros((2, *LR))
    legacy = str(jax.make_jaxpr(
        lambda l, f: executor_mod._execute(plan, l, f))(list(LAYERS), dummy))
    rules = [f.rule for f in
             program_audit.audit_jaxpr(legacy, precision="int8")]
    assert "quant_in_hot_path" in rules  # the round-trip used to trace in


def test_prepare_stack_runs_once_per_session_numerics(monkeypatch):
    """Serving many buckets and resolutions prepares the weight stack
    exactly once — preparation is keyed by (precision, backend), which a
    session fixes."""
    import repro.engine.session as session_mod

    calls = []
    real = session_mod.prepare_stack
    monkeypatch.setattr(
        session_mod, "prepare_stack",
        lambda plan, layers: (calls.append(plan.stack_key), real(plan, layers))[1],
    )
    session = engine.SRSession(LAYERS, backend="tilted", precision="int8")
    for n in (1, 2, 3):  # buckets 1, 2, 4
        session.upscale(CLIP[:n])
    session.upscale(jnp.ones((1, 24, 16, 3)))  # second resolution
    assert calls == [("int8", "tilted")]
    stacks = session.cache_stats()["stacks"]
    assert len(stacks) == 1 and stacks[0]["refs"] == 4
    assert stacks[0]["resident_bytes"] > 0 and stacks[0]["prepare_s"] >= 0


# ----------------------------------------------------------------------
# Async == sync, bit-exact, all backends x precisions
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend,precision", [
    ("reference", "fp32"),
    ("reference", "bf16"),
    ("reference", "int8"),
    ("tilted", "fp32"),
    ("tilted", "bf16"),
    ("tilted", "int8"),
    pytest.param("kernel", "fp32", marks=pytest.mark.slow),
    pytest.param("kernel", "bf16", marks=pytest.mark.slow),
    pytest.param("kernel", "int8", marks=pytest.mark.slow),
])
def test_async_vs_sync_bit_exact(backend, precision):
    """pipeline_depth >= 2 serves the SAME compiled program over the SAME
    prepared stack as depth 1 — outputs must be bit-identical.  Against the
    legacy trace-prep-into-the-call oracle, fp32/bf16 are also bit-exact;
    int8 tolerates fused-vs-eager dequant ULP differences."""
    clip = CLIP[:5] if backend == "kernel" else CLIP  # keep interpret fast
    sync = small_session(backend=backend, precision=precision,
                         pipeline_depth=1)
    deep = small_session(backend=backend, precision=precision,
                         pipeline_depth=3)
    out_sync = np.asarray(sync.upscale(clip))
    out_deep = np.asarray(deep.upscale(clip))
    np.testing.assert_array_equal(out_sync, out_deep)
    oracle = np.asarray(engine.run(sync.plan_for(LR), LAYERS, clip))
    if precision == "int8":
        np.testing.assert_allclose(out_sync, oracle, atol=2e-5, rtol=0)
    else:
        np.testing.assert_array_equal(out_sync, oracle)


@settings(max_examples=6, deadline=None)
@given(depth=st.integers(min_value=1, max_value=3),
       t=st.integers(min_value=1, max_value=6))
def test_pipeline_depth_property(depth, t):
    """Any depth serves any clip length identically to the unpipelined
    engine; depth=1 degenerates to blocking (at most ONE chunk in flight),
    and in-flight chunks never exceed the configured depth."""
    session = small_session(pipeline_depth=depth)
    out = session.upscale(CLIP[:t])
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(engine.run(session.plan_for(LR), LAYERS, CLIP[:t])))
    chunks = -(-t // 2)  # bucket capped at 2
    assert session.stats()["peak_inflight"] == min(depth, chunks)


def test_host_float64_canonicalized_to_one_program():
    """numpy's default float64 serves through the SAME compiled program as
    float32 (jax canonicalizes without x64): one cache entry, labeled with
    the dtype actually served, and a later float32 request is a pure hit."""
    session = small_session()
    out64 = session.upscale(np.asarray(CLIP, np.float64)[:2])
    out32 = session.upscale(np.asarray(CLIP, np.float32)[:2])
    s = session.cache_stats()
    assert s["misses"] == 1 and s["hits"] == 1 and s["size"] == 1
    assert s["entries"][0]["dtype"] == "float32"
    np.testing.assert_array_equal(np.asarray(out64), np.asarray(out32))


def test_host_numpy_clip_staged_chunkwise():
    """numpy input stays host-resident and is device_put chunk by chunk;
    the result matches device-array input exactly."""
    session_np = small_session()
    session_jax = small_session()
    out_np = session_np.upscale(np.asarray(CLIP))
    out_jax = session_jax.upscale(CLIP)
    np.testing.assert_array_equal(np.asarray(out_np), np.asarray(out_jax))
    assert session_np.stats()["frames"] == 7


# ----------------------------------------------------------------------
# Dispatch vs complete latency
# ----------------------------------------------------------------------
def test_sync_caller_sees_identical_dispatch_and_complete():
    session = small_session()
    plan = session.plan_for(LR)
    session.serve_batch(plan, jnp.ones((2, *LR)))
    session.serve_batch(plan, jnp.ones((2, *LR)))
    assert session.spans.values("launch") == session.spans.values("latency")
    s = session.stats()
    assert s["dispatch_mean_ms"] == s["mean_ms"]
    assert s["dispatch_p50_ms"] == s["p50_ms"]
    assert s["batches"] == 2 and s["peak_inflight"] == 1


def test_pipelined_complete_never_precedes_dispatch():
    """Per chunk, complete (dispatch -> ready) >= dispatch (enqueue only):
    both are measured from the same dispatch start."""
    session = small_session(pipeline_depth=2)
    session.upscale(CLIP)  # 4 chunks
    d = np.asarray(session.spans.values("launch"))
    c = np.asarray(session.spans.values("latency"))
    assert d.shape == c.shape == (4,)
    assert (c >= d).all()
    s = session.stats()
    assert s["peak_inflight"] == 2
    assert s["p99_ms"] >= s["p95_ms"] >= s["p50_ms"]
    assert s["frames"] == 7 and s["fps"] > 0


def test_latency_stats_p99_total_span_and_empty():
    from repro.engine.session import latency_stats

    empty = latency_stats([], 0)
    assert empty["fps"] == 0.0 and empty["p99_ms"] == 0.0
    assert empty["dispatch_mean_ms"] == 0.0
    s = latency_stats([1.0, 2.0, 3.0, 100.0], 4,
                      dispatch_ms=[0.1, 0.1, 0.1, 0.1], total_s=0.05)
    assert s["p99_ms"] >= s["p95_ms"] >= s["p50_ms"] > 0
    assert s["fps"] == pytest.approx(4 / 0.05)  # span-based, not sum-based
    assert s["dispatch_mean_ms"] == pytest.approx(0.1)
    # degenerate span (clock too coarse) stays finite
    z = latency_stats([0.0], 2, total_s=0.0)
    assert z["fps"] == 0.0 and np.isfinite(z["fps"])


# ----------------------------------------------------------------------
# Ragged tails: one staging buffer, no shape-driven recompile
# ----------------------------------------------------------------------
def test_ragged_tails_never_recompile():
    """Clips of 7, 5 and 2 frames through a bucket-4 session: every chunk
    (ragged or not) hits the ONE compiled program — one cache miss, one
    trace on the executor's own jit."""
    session = engine.SRSession(LAYERS, backend="tilted", max_bucket=4)
    session.upscale(CLIP)  # compiles the one bucket-4 program
    entry = session._cache.entries()[0]
    assert entry.jitted is not None
    traced = entry.jitted._cache_size() if hasattr(
        entry.jitted, "_cache_size") else None
    for t in (5, 6):  # tails of 1 and 2 — same bucket, same program
        out = session.upscale(CLIP[:t])
        assert out.shape == (t, 36, 48, 3)
    s = session.cache_stats()
    assert s["misses"] == 1 and s["size"] == 1
    if traced is not None:  # no shape-driven retrace across ragged tails
        assert entry.jitted._cache_size() == traced
    # the tail staging buffer is reused, not reallocated per ragged tail
    np_session = engine.SRSession(LAYERS, backend="tilted", max_bucket=4)
    np_session.upscale(np.asarray(CLIP))  # tail 3 -> staging buffer built
    key, buf = np_session._staging
    np_session.upscale(np.asarray(CLIP[:5]))  # tail 1 -> SAME buffer
    assert np_session._staging[1] is buf
    np.testing.assert_array_equal(
        np.asarray(np_session.upscale(np.asarray(CLIP))),
        np.asarray(engine.run(np_session.plan_for(LR), LAYERS, CLIP)))


def test_padding_does_not_leak_into_real_frames():
    """Padded tail frames never contaminate real outputs (device path uses
    one fused jnp.pad, host path a zeroed staging buffer)."""
    session = small_session()
    out = session.upscale(CLIP[:3])  # chunks: 2 + 1(padded)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(engine.run(session.plan_for(LR), LAYERS, CLIP[:3])))


# ----------------------------------------------------------------------
# PreparedStack lifecycle: refcounts, eviction, clear
# ----------------------------------------------------------------------
def test_eviction_releases_stack_reference():
    """Evicting a cache entry releases its reference on the shared
    PreparedStack — refs always equal the number of LIVE entries, so
    churning resolutions through a small cache cannot leak weight
    buffers."""
    session = engine.SRSession(LAYERS, backend="tilted", precision="int8",
                               cache_capacity=1)
    session.upscale(jnp.ones((1, *LR)))
    assert session.cache_stats()["stacks"][0]["refs"] == 1
    session.upscale(jnp.ones((1, 24, 16, 3)))  # evicts the (12,16) entry
    s = session.cache_stats()
    assert s["evictions"] == 1 and s["size"] == 1
    assert s["stacks"][0]["refs"] == 1  # released on evict, not 2
    assert session._stacks[("int8", "tilted")].refs == 1


def test_clear_cache_frees_device_resident_weights():
    """clear_cache evicts every executor AND drops the prepared weight
    buffers (live-array count falls); the next request re-prepares and
    serves correctly."""
    session = engine.SRSession(LAYERS, backend="tilted", precision="int8")
    out = session.upscale(jnp.ones((2, *LR)))
    del out
    gc.collect()
    live_before = len(jax.live_arrays())
    assert len(session._stacks) == 1
    session.clear_cache()
    gc.collect()
    assert session._stacks == {}
    assert len(jax.live_arrays()) < live_before  # prepared weights freed
    assert session.cache_stats()["size"] == 0
    out = session.upscale(jnp.ones((2, *LR)))  # re-prepares + recompiles
    assert out.shape == (2, 36, 48, 3)


# ----------------------------------------------------------------------
# Donation
# ----------------------------------------------------------------------
def test_donating_executor_matches_non_donating():
    """donate_frames compiles with the batch donated; on CPU XLA ignores
    donation (with a warning) but the program must stay correct."""
    plan = engine.make_plan(LAYERS, LR, band_rows=12, backend="tilted")
    stack = engine.prepare_stack(plan, LAYERS)
    frames = jax.random.uniform(jax.random.PRNGKey(12), (2, *LR))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # cpu: "donated buffers not usable"
        fn = engine.build_stack_executor(plan, stack, donate_frames=True)
        out = np.asarray(fn(frames))
    assert fn.donates_frames
    np.testing.assert_array_equal(
        out, np.asarray(engine.run(plan, LAYERS, frames)))


def test_session_donation_gating_and_caller_safety():
    """donate_frames=None resolves per-backend (off on CPU); with donation
    forced on, upscale still never consumes the CALLER's array — only
    session-staged slabs are donated."""
    auto = engine.SRSession(LAYERS)
    assert auto._resolve_donate() == (jax.default_backend() != "cpu")
    assert engine.SRSession(LAYERS, donate_frames=True)._resolve_donate()
    forced = engine.SRSession(LAYERS, donate_frames=True, max_bucket=2)
    clip = jax.random.uniform(jax.random.PRNGKey(13), (2, *LR))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        first = np.asarray(forced.upscale(clip))   # exact-fit chunk is clip
        second = np.asarray(forced.upscale(clip))  # clip must still be live
    np.testing.assert_array_equal(first, second)
    assert forced.cache_stats()["entries"][0]["donates"] is True


# ----------------------------------------------------------------------
# Kernel backend: pre-packed weights (ops-level)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_ops_pack_stack_matches_inline_packing():
    from repro.kernels import ops

    x = jax.random.uniform(jax.random.PRNGKey(14), (2, 12, 16, 3))
    inline = ops.tilted_fused_frames(x, LAYERS, band_rows=12)
    packed = ops.pack_stack(LAYERS, dtype=jnp.float32)
    pre = ops.tilted_fused_frames(x, band_rows=12, packed=packed,
                                  compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(inline), np.asarray(pre))
    with pytest.raises(ValueError, match="layers or packed"):
        ops.tilted_fused_frames(x, band_rows=12)


def test_video_stream_pins_blocking_depth():
    """The deprecated shim keeps legacy semantics: depth 1, no donation."""
    plan = engine.make_plan(LAYERS, (12, 16, 3), band_rows=12,
                            backend="tilted")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        stream = engine.VideoStream(plan, LAYERS, batch_size=2)
    assert stream.session.pipeline_depth == 1
    assert stream.session._resolve_donate() is False
    hr = stream.run(jax.random.uniform(jax.random.PRNGKey(15), (5, 12, 16, 3)))
    assert hr.shape == (5, 36, 48, 3)
    assert stream.session.stats()["peak_inflight"] == 1
