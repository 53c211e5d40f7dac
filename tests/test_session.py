"""SRSession serving API: plan derivation, batch bucketing, PlanCache LRU,
session/stream parity, empty-clip dtype, warmup dtype.  All fast tier."""

import os
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
from repro.engine.plan import (
    SRPlan,
    derive_band_rows,
    legal_band_rows,
    shardable_band_rows,
)
from repro.engine.session import PlanCache, bucket_batch
from repro.models.abpn import ABPNConfig, init_abpn
from repro.models.registry import get_sr_model, register_sr_model

CFG = ABPNConfig()
LAYERS = init_abpn(jax.random.PRNGKey(2), CFG)


def make_stream(plan, layers, batch_size, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return engine.VideoStream(plan, layers, batch_size, **kw)


# ----------------------------------------------------------------------
# Batch bucketing + band_rows derivation (property-style)
# ----------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=1, max_value=4096))
def test_bucket_batch_rounds_to_next_power_of_two(n):
    b = bucket_batch(n)
    assert b >= n
    assert b & (b - 1) == 0  # power of two
    assert b // 2 < n  # the NEXT power of two, not a later one


def test_bucket_batch_rejects_nonpositive():
    with pytest.raises(ValueError):
        bucket_batch(0)


@settings(max_examples=60, deadline=None)
@given(h=st.integers(min_value=1, max_value=2000))
def test_derive_band_rows_always_legal(h):
    r = derive_band_rows(h)
    assert h % r == 0  # banded backends need an even partition
    # either near the paper's 60-row design point or one full-height band
    assert r <= 60 or r == h


def test_derive_band_rows_design_points():
    assert derive_band_rows(360) == 60  # the paper's frame
    assert derive_band_rows(120) == 60
    assert derive_band_rows(64) == 32
    assert derive_band_rows(97) == 97  # prime: one band, no slivers
    assert derive_band_rows(6) == 6


def test_legal_band_rows_all_divisors_sorted_by_preference():
    cands = legal_band_rows(120)
    assert all(120 % d == 0 for d in cands)
    assert cands[0] == 60  # nearest the paper's design point
    assert set(cands) == {8, 10, 12, 15, 20, 24, 30, 40, 60, 120}
    # distance from preferred is non-decreasing
    dist = [abs(d - 60) for d in cands]
    assert dist == sorted(dist)


def test_legal_band_rows_prime_height_only_full_band():
    assert legal_band_rows(127) == [127]


def test_derive_band_rows_matches_legacy_semantics():
    assert derive_band_rows(360) == 60
    assert derive_band_rows(120) == 60
    assert derive_band_rows(80) == 40
    assert derive_band_rows(62) == 31
    assert derive_band_rows(24) == 24
    assert derive_band_rows(127) == 127  # prime: one giant band


def test_prime_height_warns_and_flags_degenerate():
    with pytest.warns(RuntimeWarning, match="ONE 127-row band"):
        plan = SRPlan.from_request((127, 16, 3), num_layers=len(LAYERS))
    assert plan.degenerate_bands is True
    assert plan.band_rows == 127
    # metadata only: equal to the same plan without the flag, same hash
    twin = SRPlan.from_request((127, 16, 3), num_layers=len(LAYERS),
                               band_rows=127)
    assert twin.degenerate_bands is False
    assert plan == twin and hash(plan) == hash(twin)


def test_non_degenerate_heights_do_not_warn():
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = SRPlan.from_request((120, 16, 3), num_layers=len(LAYERS))
    assert plan.degenerate_bands is False


# LR frames users send (16:9 at 240p..1080p); the 4K cell's 720p frames
# are served over 4 band shards, and so could 1080p's.
SERVED = [((240, 426), 1), ((360, 640), 1), ((480, 854), 1),
          ((540, 960), 1), ((720, 1280), 1), ((1080, 1920), 1),
          ((720, 1280), 4), ((1080, 1920), 4)]


@pytest.mark.parametrize(
    "hw,band_shards", SERVED,
    ids=[f"{h}x{w}-band{s}" for (h, w), s in SERVED])
def test_plan_derivation_at_served_heights(hw, band_shards):
    """At every served height the derived band is a legal, non-degenerate
    divisor and the first preference (on a mesh: the first one whose band
    count splits over the shards), and each band sweeps in one tile."""
    H, W = hw
    legal = legal_band_rows(H)
    if band_shards == 1:
        derived = SRPlan.from_request((H, W, 3), num_layers=len(LAYERS))
        assert not derived.degenerate_bands
        band_rows, first = derived.band_rows, legal[0]
    else:
        band_rows = shardable_band_rows(H, band_shards)
        first = next(d for d in legal if (H // d) % band_shards == 0)
        assert (H // band_rows) % band_shards == 0
    assert band_rows in legal and band_rows == first
    assert not (band_rows == H and H > 60)  # never the one-band fallback
    plan = SRPlan.from_request((H, W, 3), num_layers=len(LAYERS),
                               band_rows=band_rows)
    assert plan.schedule.num_tiles == 1


@pytest.fixture(scope="module")
def halo_default():
    """Frames and the derived-default session's ``halo`` output."""
    frames = np.random.default_rng(2).random((2, 120, 16, 3), np.float32)
    session = engine.SRSession(LAYERS, vertical_policy="halo")
    return frames, np.asarray(session.upscale(frames))


@pytest.mark.slow
@pytest.mark.parametrize("band_rows", legal_band_rows(120))
def test_halo_output_is_independent_of_band_rows(band_rows, halo_default):
    """Under ``halo`` every legal band decomposition recomputes its
    neighbours' rows exactly, so the output is the derived default's bit
    for bit."""
    frames, want = halo_default
    got = engine.SRSession(LAYERS, vertical_policy="halo",
                           band_rows=band_rows).upscale(frames)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pipeline_depth_defaults_to_two_and_explicit_is_kept():
    assert engine.SRSession(LAYERS).pipeline_depth == 2
    session = engine.SRSession(LAYERS, pipeline_depth=3)
    session.upscale(np.zeros((5, 12, 16, 3), np.float32))
    assert session.pipeline_depth == 3


def test_serving_writes_nothing_under_home(tmp_path, monkeypatch):
    """The schedule is derived, never read from or written to a user
    directory: serving leaves an empty HOME and XDG cache empty."""
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    frames = np.zeros((3, 12, 16, 3), np.float32)
    engine.SRSession(LAYERS).upscale(frames)
    with engine.SRServer.open("abpn_x3", layers=LAYERS) as server:
        server.submit(frames).result()
    assert os.listdir(tmp_path) == []


def test_plan_from_request_derives_geometry():
    plan = engine.SRPlan.from_request((120, 64, 3), num_layers=7)
    assert (plan.band_rows, plan.num_bands) == (60, 2)
    explicit = engine.SRPlan.from_request((120, 64, 3), num_layers=7,
                                          band_rows=30)
    assert explicit.num_bands == 4
    with pytest.raises(ValueError):
        engine.SRPlan.from_request((120, 64), num_layers=7)  # not (H, W, C)


# ----------------------------------------------------------------------
# PlanCache: LRU order, eviction, counters
# ----------------------------------------------------------------------
def test_plan_cache_lru_eviction_order():
    cache = PlanCache(capacity=2)
    cache.put("a", "A")
    cache.put("b", "B")
    assert cache.get("a") == "A"  # bumps a to MRU
    cache.put("c", "C")  # evicts b (LRU), not a
    assert cache.keys() == ["a", "c"]
    assert "b" not in cache and cache.evictions == 1
    assert cache.get("b") is None  # miss


def test_plan_cache_counters_and_stats():
    cache = PlanCache(capacity=3)
    assert cache.get("x") is None  # miss on empty
    cache.put("x", 1)
    assert cache.get("x") == 1 and cache.get("x") == 1  # two hits
    s = cache.stats()
    assert s["hits"] == 2 and s["misses"] == 1 and s["evictions"] == 0
    assert s["size"] == 1 and s["capacity"] == 3
    assert s["hit_rate"] == pytest.approx(2 / 3)
    with pytest.raises(ValueError):
        PlanCache(capacity=0)


@settings(max_examples=20, deadline=None)
@given(capacity=st.integers(min_value=1, max_value=6),
       n_keys=st.integers(min_value=1, max_value=12))
def test_plan_cache_never_exceeds_capacity(capacity, n_keys):
    cache = PlanCache(capacity=capacity)
    for k in range(n_keys):
        cache.put(k, k)
    assert len(cache) == min(capacity, n_keys)
    assert cache.evictions == max(0, n_keys - capacity)
    # survivors are the most recently inserted keys, oldest first
    assert cache.keys() == list(range(max(0, n_keys - capacity), n_keys))


# ----------------------------------------------------------------------
# SRSession serving (the acceptance scenario)
# ----------------------------------------------------------------------
def test_session_serves_mixed_resolutions_and_batches():
    """One session, three resolutions x two batch sizes, no user-visible
    plan construction: exactly one compile per (plan, bucket), hits on
    repeats."""
    session = engine.SRSession.open("abpn_x3", layers=LAYERS, backend="tilted")
    resolutions = [(12, 16, 3), (24, 16, 3), (36, 8, 3)]
    batch_sizes = (1, 3)  # buckets 1 and 4
    for _ in range(2):  # second pass must be all cache hits
        for (h, w, c) in resolutions:
            for bs in batch_sizes:
                frames = jnp.ones((bs, h, w, c))
                hr = session.upscale(frames)
                assert hr.shape == (bs, 3 * h, 3 * w, c)
    s = session.cache_stats()
    assert s["misses"] == len(resolutions) * len(batch_sizes)  # one compile each
    assert s["hits"] == len(resolutions) * len(batch_sizes)
    assert s["evictions"] == 0 and s["size"] == 6
    assert sorted({(tuple(e["lr_shape"]), e["bucket"]) for e in s["entries"]}) == \
        sorted((r, engine.bucket_batch(b)) for r in resolutions for b in batch_sizes)
    assert all(e["compile_s"] > 0 for e in s["entries"])
    st_ = session.stats()
    assert st_["frames"] == 2 * sum(batch_sizes) * len(resolutions)


def test_session_rank_handling_matches_flat_batch():
    session = engine.SRSession(LAYERS, backend="tilted")
    frames = jax.random.uniform(jax.random.PRNGKey(5), (4, 12, 16, 3))
    flat = session.upscale(frames)
    single = session.upscale(frames[0])  # (H, W, C)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(flat[0]))
    nested = session.upscale(frames.reshape(2, 2, 12, 16, 3))  # (B, T, ...)
    assert nested.shape == (2, 2, 36, 48, 3)
    np.testing.assert_array_equal(
        np.asarray(nested.reshape(4, 36, 48, 3)), np.asarray(flat))
    with pytest.raises(ValueError):
        session.upscale(jnp.ones((12, 16)))  # rank 2
    with pytest.raises(ValueError):
        session.upscale(jnp.ones((2, 12, 16, 4)))  # channel mismatch


def test_session_bucket_padding_parity():
    """A batch that is not a power of two is padded to its bucket; padding
    must not leak into the real frames' output."""
    session = engine.SRSession(LAYERS, backend="tilted")
    frames = jax.random.uniform(jax.random.PRNGKey(6), (3, 12, 16, 3))
    out3 = session.upscale(frames)  # bucket 4, one padded frame
    plan = session.plan_for((12, 16, 3))
    np.testing.assert_array_equal(
        np.asarray(out3), np.asarray(engine.run(plan, LAYERS, frames)))


@pytest.mark.parametrize("n,max_bucket,bucket,chunks", [
    (8, 5, 4, 2),     # the cap clamps DOWN to a power of two
    (3, None, 4, 1),  # no cap: the next power of two
    (5, None, 8, 1),
    (1, 4, 1, 1),
    (6, 4, 4, 2),     # the tail keeps the first chunk's program
    (9, 8, 8, 2),
])
def test_session_max_bucket_is_a_ceiling(n, max_bucket, bucket, chunks):
    """max_bucket is never exceeded: the bucket clamps DOWN to the largest
    power of two within the cap and larger requests chunk."""
    session = engine.SRSession(LAYERS, backend="tilted",
                               max_bucket=max_bucket)
    frames = jax.random.uniform(jax.random.PRNGKey(8), (n, 12, 16, 3))
    out = session.upscale(frames)
    assert out.shape == (n, 36, 48, 3)
    entries = session.cache_stats()["entries"]
    assert [e["bucket"] for e in entries] == [bucket]
    assert session.stats()["batches"] == chunks
    plan = session.plan_for((12, 16, 3))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(engine.run(plan, LAYERS, frames)))


def test_session_matches_video_stream_on_identical_input():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30,
                            backend="tilted")
    stream = make_stream(plan, LAYERS, batch_size=2)
    session = engine.SRSession(LAYERS, backend="tilted", band_rows=30)
    frames = jax.random.uniform(jax.random.PRNGKey(7), (5, 60, 32, 3))
    np.testing.assert_array_equal(
        np.asarray(session.upscale(frames)), np.asarray(stream.run(frames)))


def test_session_lru_eviction_keeps_serving():
    session = engine.SRSession(LAYERS, backend="tilted", cache_capacity=1)
    a = jnp.ones((1, 12, 16, 3))
    b = jnp.ones((1, 24, 16, 3))
    session.upscale(a)
    session.upscale(b)  # evicts the (12, 16) entry
    out = session.upscale(a)  # recompiles, still correct
    assert out.shape == (1, 36, 48, 3)
    s = session.cache_stats()
    assert s["evictions"] == 2 and s["size"] == 1 and s["misses"] == 3


def test_session_empty_request_matches_compiled_dtype():
    session = engine.SRSession(LAYERS, backend="tilted")
    for dtype in (jnp.float32, jnp.bfloat16):
        full = session.upscale(jnp.ones((1, 12, 16, 3), dtype))
        empty = session.upscale(jnp.zeros((0, 12, 16, 3), dtype))
        assert empty.shape == (0, 36, 48, 3)
        assert empty.dtype == full.dtype
    nested = session.upscale(jnp.zeros((2, 0, 12, 16, 3)))
    assert nested.shape == (2, 0, 36, 48, 3)


def test_session_open_resolves_registry_and_unknown_model():
    spec = get_sr_model("abpn_x3")
    assert spec is get_sr_model("abpn-x3")  # alias
    assert len(spec.init(jax.random.PRNGKey(0))) == CFG.num_layers
    session = engine.SRSession.open("abpn", seed=3)
    assert session.model == "abpn_x3" and session.scale == CFG.scale
    with pytest.raises(ValueError, match="unknown SR model"):
        engine.SRSession.open("espcn_x4")
    with pytest.raises(ValueError, match="layer stack is empty"):
        engine.SRSession([])


def test_register_sr_model_collision_leaves_registry_untouched():
    with pytest.raises(ValueError, match="already registered"):
        register_sr_model("espcn_x4", CFG, init_abpn, aliases=("abpn",))
    with pytest.raises(ValueError, match="unknown SR model"):
        get_sr_model("espcn_x4")  # the failed call must not half-register


# ----------------------------------------------------------------------
# VideoStream shim: empty-clip dtype + warmup dtype (the two bugfixes)
# ----------------------------------------------------------------------
def test_video_stream_is_deprecated():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30)
    with pytest.warns(DeprecationWarning):
        engine.VideoStream(plan, LAYERS, batch_size=1)


def test_video_stream_empty_clip_dtype_matches_compiled_output():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30,
                            backend="tilted")
    stream = make_stream(plan, LAYERS, batch_size=2)
    for dtype in (jnp.float32, jnp.bfloat16):
        full = stream.process(jnp.ones((2, 60, 32, 3), dtype))
        empty = stream.run(jnp.zeros((0, 60, 32, 3), dtype))
        assert empty.dtype == full.dtype
        assert empty.shape == (0, 180, 96, 3)


def test_video_stream_warmup_compiles_serving_dtype():
    """Warming up in the serving dtype means the first real batch is a
    cache hit — no second compile counted as serving latency."""
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30,
                            backend="tilted")
    stream = make_stream(plan, LAYERS, batch_size=2, dtype=jnp.bfloat16)
    compile_s = stream.warmup()
    assert compile_s > 0
    stream.process(jnp.ones((2, 60, 32, 3), jnp.bfloat16))
    s = stream.cache_stats()
    assert s["misses"] == 1 and s["hits"] == 1  # one compile, then a hit
    assert s["entries"][0]["dtype"] == "bfloat16"
    # a batch in a different dtype compiles separately (outside the timed
    # region), it does not silently recompile the warm entry
    stream.process(jnp.ones((2, 60, 32, 3), jnp.float32))
    s = stream.cache_stats()
    assert s["misses"] == 2 and s["size"] == 2
    assert s["entries"][-1]["dtype"] == "float32"
