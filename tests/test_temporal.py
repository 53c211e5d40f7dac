"""Temporal delta serving: band diffing, output cache, splice parity.

Fast tier: digest/dilation/slab geometry units (cross-checked against
``core.fusion.halo_slabs``, the one true halo geometry), OutputBandCache
LRU + pin semantics, the ``verify_delta_cover`` plan_check rule,
partial-band dispatch plumbing (``submit_bands`` -> ``band_subset``),
the DeltaSession parity matrix on the tilted backend, stream cleanup
leak tests, and the registry error-message satellite.  Slow tier:
kernel-backend delta parity (interpret-mode Pallas) and the mesh
subprocess parity proof.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro import engine
from repro.analysis.plan_check import verify_delta_cover
from repro.core.fusion import halo_slabs
from repro.engine.server import RequestCancelledError, SRServer
from repro.engine.temporal import (
    DeltaSession,
    OutputBandCache,
    band_bounds,
    band_digest,
    band_digests,
    band_input_rows,
    band_slabs,
    changed_bands,
    dilate_dirty,
    halo_reach,
    window_digest,
    window_rows,
)
from repro.models.abpn import ABPNConfig, init_abpn
from repro.models.registry import get_sr_model

CFG = ABPNConfig()
LAYERS = init_abpn(jax.random.PRNGKey(2), CFG)
LR = (24, 16, 3)          # band_rows=6 -> 4 bands; halo reach ceil(7/6)=2
BAND_ROWS = 6
L = CFG.num_layers

RNG = np.random.default_rng(7)
FRAME = RNG.random(LR, dtype=np.float32)


def make_session(**kw):
    kw.setdefault("backend", "tilted")
    kw.setdefault("band_rows", BAND_ROWS)
    return engine.SRSession(LAYERS, **kw)


def clip_with_motion(frames: int = 4) -> list:
    """f0, f0 again (static), one-band change, then a fresh frame."""
    clip = [FRAME.copy(), FRAME.copy()]
    f2 = FRAME.copy()
    f2[2 * BAND_ROWS : 2 * BAND_ROWS + 2] += 0.25  # band 2 only
    clip.append(f2)
    clip.append(RNG.random(LR, dtype=np.float32))
    return clip[:frames]


# ----------------------------------------------------------------------
# band_diff: digests, dilation, geometry
# ----------------------------------------------------------------------
def test_halo_reach():
    assert halo_reach(60, 7, "halo") == 1     # the paper's design point
    assert halo_reach(7, 7, "halo") == 1
    assert halo_reach(6, 7, "halo") == 2
    assert halo_reach(3, 7, "halo") == 3
    assert halo_reach(6, 7, "zero") == 0
    assert halo_reach(6, 7, "replicate") == 0


def test_band_digest_localises_changes():
    own = band_digests(FRAME, BAND_ROWS)
    assert len(own) == LR[0] // BAND_ROWS
    bumped = FRAME.copy()
    bumped[BAND_ROWS + 1, 3] += 1.0  # one pixel inside band 1
    assert changed_bands(band_digests(bumped, BAND_ROWS), own) == {1}
    assert changed_bands(own, own) == set()


def test_digest_folds_dtype():
    # same raw bytes under a different dtype must not collide
    zeros32 = np.zeros((BAND_ROWS, 4, 1), np.float32)
    zeros_i = np.zeros((BAND_ROWS, 4, 1), np.int32)
    assert zeros32.tobytes() == zeros_i.tobytes()
    assert band_digest(zeros32, BAND_ROWS, 0) != band_digest(
        zeros_i, BAND_ROWS, 0)


def test_band_digests_rejects_ragged_height():
    with pytest.raises(ValueError, match="not a multiple"):
        band_digests(FRAME, 7)


def test_changed_bands_rejects_band_count_change():
    with pytest.raises(ValueError, match="digest count changed"):
        changed_bands(band_digests(FRAME, BAND_ROWS),
                      band_digests(FRAME, 12))


def test_dilate_dirty_clips_and_validates():
    # reach 2 at R=6, L=7: band 1 dirties [0, 3]; band 3 dirties [1, 3]
    assert dilate_dirty({1}, 4, BAND_ROWS, L, "halo") == {0, 1, 2, 3}
    assert dilate_dirty({3}, 4, BAND_ROWS, L, "halo") == {1, 2, 3}
    assert dilate_dirty({2}, 4, BAND_ROWS, L, "zero") == {2}
    assert dilate_dirty(set(), 4, BAND_ROWS, L, "halo") == set()
    with pytest.raises(ValueError, match="out of range"):
        dilate_dirty({4}, 4, BAND_ROWS, L, "halo")


def test_dilation_invariant_protects_clean_windows():
    """The invariant the splice relies on: a band OUTSIDE the dilated
    dirty set has a byte-identical receptive-field window."""
    h = LR[0]
    num_bands = h // BAND_ROWS
    for policy in ("zero", "halo", "replicate"):
        for changed in range(num_bands):
            bumped = FRAME.copy()
            bumped[changed * BAND_ROWS] += 1.0
            dirty = dilate_dirty({changed}, num_bands, BAND_ROWS, L, policy)
            for b in range(num_bands):
                if b in dirty:
                    continue
                assert window_digest(
                    FRAME, BAND_ROWS, L, b, policy
                ) == window_digest(bumped, BAND_ROWS, L, b, policy), (
                    f"clean band {b} window changed ({policy}, "
                    f"changed={changed})"
                )


def test_window_rows_halo_widens_and_clips():
    assert window_rows(24, 6, 7, 0, "halo") == (0, 13)
    assert window_rows(24, 6, 7, 2, "halo") == (5, 24)
    assert window_rows(24, 6, 7, 1, "zero") == (6, 12)


def test_band_slabs_and_bounds_mirror_halo_slabs():
    """The host marshalling must be byte-identical to the device-side
    ``core.fusion.halo_slabs`` geometry — the bit-exact splice guarantee
    starts at this equality."""
    ref_slabs, ref_bounds = halo_slabs(FRAME[None], BAND_ROWS, L)
    all_bands = list(range(LR[0] // BAND_ROWS))
    mine = band_slabs(FRAME, BAND_ROWS, L, all_bands, "halo")
    np.testing.assert_array_equal(mine, np.asarray(ref_slabs))
    bounds = band_bounds(LR[0], BAND_ROWS, L, all_bands)
    np.testing.assert_array_equal(bounds, np.asarray(ref_bounds))
    # a subset picks exactly those rows of the full marshalling
    subset = [0, 2]
    np.testing.assert_array_equal(
        band_slabs(FRAME, BAND_ROWS, L, subset, "halo"),
        np.asarray(ref_slabs)[subset])
    # padded slots are all-phantom (0, 0): never read back
    padded = band_bounds(LR[0], BAND_ROWS, L, subset, slots=4)
    assert padded.shape == (4, 2)
    np.testing.assert_array_equal(padded[2:], 0)
    # zero/replicate slabs are the plain band rows
    assert band_input_rows(BAND_ROWS, L, "zero") == BAND_ROWS
    np.testing.assert_array_equal(
        band_slabs(FRAME, BAND_ROWS, L, [1], "zero")[0],
        FRAME[BAND_ROWS : 2 * BAND_ROWS])


# ----------------------------------------------------------------------
# OutputBandCache
# ----------------------------------------------------------------------
def band_value(seed: int, nbytes: int = 1024) -> np.ndarray:
    return np.full(nbytes // 4, float(seed), np.float32)


def test_cache_lru_eviction_bound():
    cache = OutputBandCache(max_bytes=2048)
    cache.put("a", band_value(1))
    cache.put("b", band_value(2))
    assert cache.get("a") is not None  # refresh: "b" is now LRU
    cache.put("c", band_value(3))
    s = cache.stats()
    assert s["bytes"] <= 2048 and s["evictions"] == 1
    assert cache.peek("b") is None and cache.peek("a") is not None


def test_cache_put_copies_and_dedupes():
    cache = OutputBandCache(max_bytes=1 << 20)
    src = band_value(1)
    cache.put("k", src)
    src[:] = -1.0  # mutating the source must not reach the cache
    np.testing.assert_array_equal(cache.get("k"), band_value(1))
    cache.put("k", band_value(9))  # same key: no-op, same bytes by contract
    assert cache.stats()["puts"] == 1
    np.testing.assert_array_equal(cache.peek("k"), band_value(1))


def test_cache_pins_block_eviction():
    cache = OutputBandCache(max_bytes=1024)
    cache.put("a", band_value(1))
    cache.pin("a")
    cache.put("b", band_value(2))  # over budget: the unpinned "b" goes
    assert cache.peek("a") is not None and cache.peek("b") is None
    # pin=True is atomic with the insert: the entry survives the
    # eviction pass its own insert triggers
    cache.put("b", band_value(2), pin=True)
    cache.put("c", band_value(3))
    s = cache.stats()
    assert cache.peek("a") is not None and cache.peek("b") is not None
    assert s["bytes"] > s["max_bytes"] and s["pinned"] == 2  # visible overrun
    cache.unpin("a")
    cache.unpin("b")  # back to evictable -> budget enforced again
    assert cache.stats()["bytes"] <= 1024
    assert cache.pinned == 0


def test_cache_pin_errors():
    cache = OutputBandCache(max_bytes=1024)
    with pytest.raises(KeyError):
        cache.pin("missing")
    cache.put("a", band_value(1))
    with pytest.raises(ValueError, match="unbalanced"):
        cache.unpin("a")
    with pytest.raises(ValueError, match="positive"):
        OutputBandCache(max_bytes=0)


def test_cache_counters():
    cache = OutputBandCache(max_bytes=1 << 20)
    assert cache.get("a") is None
    cache.put("a", band_value(1))
    cache.get("a")
    cache.peek("a")  # peek is uncounted
    s = cache.stats()
    assert (s["hits"], s["misses"]) == (1, 1)
    assert s["hit_rate"] == 0.5
    assert s["bytes_saved"] == band_value(1).nbytes
    # get(pin=True) pins atomically with the hit; a miss pins nothing
    assert cache.get("a", pin=True) is not None
    assert cache.pinned == 1
    assert cache.get("missing", pin=True) is None
    cache.unpin("a")
    assert cache.pinned == 0


# ----------------------------------------------------------------------
# plan_check: the splice invariant rule
# ----------------------------------------------------------------------
def delta_plan(policy="halo"):
    return engine.make_plan(LAYERS, LR, band_rows=BAND_ROWS,
                            backend="tilted", vertical_policy=policy)


def test_verify_delta_cover_accepts_valid_partition():
    assert verify_delta_cover(delta_plan(), [1, 2, 3],
                              changed_bands=[3]) == []
    assert verify_delta_cover(delta_plan("zero"), [2],
                              changed_bands=[2]) == []
    assert verify_delta_cover(delta_plan(), []) == []  # nothing changed


def test_verify_delta_cover_flags_bad_sets():
    dup = verify_delta_cover(delta_plan(), [1, 1, 2])
    assert [f.rule for f in dup] == ["delta_cover"]
    oob = verify_delta_cover(delta_plan(), [4])
    assert [f.rule for f in oob] == ["delta_cover"]
    assert all(f.severity == "error" for f in dup + oob)


def test_verify_delta_cover_flags_missing_dilation():
    # band 3 changed, reach 2 -> bands 1..3 must be dirty; {3} is stale
    stale = verify_delta_cover(delta_plan(), [3], changed_bands=[3])
    assert "delta_dilation" in [f.rule for f in stale]
    # zero policy: reach 0, {3} alone is fine
    assert verify_delta_cover(delta_plan("zero"), [3],
                              changed_bands=[3]) == []


# ----------------------------------------------------------------------
# submit_bands: partial dispatches through the scheduler
# ----------------------------------------------------------------------
def test_submit_bands_matches_full_upscale_rows():
    session = make_session(vertical_policy="halo")
    with SRServer({"abpn": session}) as server:
        full = np.asarray(session.upscale(FRAME))
        plan = session.plan_for(LR)
        subset = [0, 2]
        slabs = band_slabs(FRAME, BAND_ROWS, L, subset, "halo")
        out = np.asarray(server.submit_bands(
            slabs, subset, plan=plan).result())
        hr = BAND_ROWS * plan.scale
        for i, b in enumerate(subset):
            np.testing.assert_array_equal(
                out[i], full[b * hr : (b + 1) * hr])
        # the dispatch is tagged as a band subset in the scheduler log
        recent = server.scheduler_stats()["recent_dispatches"]
        assert recent[-1]["bands"] == list(subset)


def test_submit_bands_validation():
    session = make_session(vertical_policy="halo")
    with SRServer({"abpn": session}) as server:
        plan = session.plan_for(LR)
        slabs = band_slabs(FRAME, BAND_ROWS, L, [0, 1], "halo")
        with pytest.raises(ValueError, match="strictly increasing"):
            server.submit_bands(slabs, [1, 0], plan=plan)
        with pytest.raises(ValueError, match="range"):
            server.submit_bands(slabs, [3, 4], plan=plan)
        with pytest.raises(ValueError):
            server.submit_bands(slabs[:, :-1], [0, 1], plan=plan)


def test_cancel_fails_future_and_releases_queue():
    session = make_session()
    with SRServer({"abpn": session}) as server:
        fut = server.submit(FRAME[None])
        assert server.cancel(fut) is True
        assert isinstance(fut.exception(), RequestCancelledError)
        g = server.scheduler_stats()
        assert g["pending_frames"] == 0 and g["carry_buckets"] == 0
        # a resolved future cannot be cancelled
        done = server.submit(FRAME[None])
        done.result()
        assert server.cancel(done) is False


# ----------------------------------------------------------------------
# DeltaSession: parity + reuse
# ----------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["zero", "halo", "replicate"])
def test_delta_session_bit_exact_and_reuses(policy):
    session = make_session(vertical_policy=policy)
    clip = clip_with_motion()
    with DeltaSession(session) as ds:
        for frame in clip:
            out = ds.serve(frame)
            np.testing.assert_array_equal(
                out, np.asarray(session.upscale(frame)))
    t = session.temporal_stats()
    assert t["frames"] == len(clip)
    assert t["bands_skipped"] > 0 and 0 < t["reuse_ratio"] < 1
    assert t["band_rows_served"] < t["band_rows_total"]
    assert t["band_rows_dispatched"] == t["band_rows_served"]
    assert t["cover_violations"] == 0
    assert t["cache"]["hits"] == t["bands_skipped"]
    # the static frame reused EVERYTHING: frame 1 served 0 bands
    num_bands = LR[0] // BAND_ROWS
    assert t["bands_skipped"] >= num_bands
    # stats() exposes the section once delta frames were served
    assert session.stats()["temporal"]["frames"] == len(clip)


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["zero", "halo"])
def test_delta_session_kernel_backend_bit_exact(policy):
    # a shallow stack keeps interpret-mode Pallas time bounded
    cfg = ABPNConfig(num_layers=3)
    layers = init_abpn(jax.random.PRNGKey(4), cfg)
    session = engine.SRSession(layers, backend="kernel", band_rows=6,
                               vertical_policy=policy)
    clip = [FRAME.copy(), FRAME.copy(), clip_with_motion(3)[2]]
    with DeltaSession(session) as ds:
        for frame in clip:
            np.testing.assert_array_equal(
                ds.serve(frame), np.asarray(session.upscale(frame)))
    assert session.temporal_stats()["bands_skipped"] > 0


def test_delta_session_rejects_reference_backend():
    session = engine.SRSession(LAYERS, backend="reference")
    with pytest.raises(ValueError, match="banded backend"):
        DeltaSession(session)
    ref_plan = engine.make_plan(LAYERS, LR, band_rows=BAND_ROWS,
                                backend="reference")
    with pytest.raises(ValueError, match="reference"):
        make_session().band_executor_for(ref_plan, 1, np.float32)


def test_delta_session_plan_switch_resets_state():
    session = make_session(vertical_policy="halo")
    small = RNG.random((12, 16, 3), dtype=np.float32)
    with DeltaSession(session) as ds:
        ds.serve(FRAME)
        out = ds.serve(small)  # resolution switch mid-stream
        np.testing.assert_array_equal(
            out, np.asarray(session.upscale(small)))
        # pins now belong to the new plan's bands only
        assert session.output_cache().pinned == 12 // BAND_ROWS
        # and returning to the first resolution serves full (state reset)
        np.testing.assert_array_equal(
            ds.serve(FRAME), np.asarray(session.upscale(FRAME)))
    assert session.output_cache().pinned == 0


def test_delta_session_close_semantics():
    session = make_session()
    ds = DeltaSession(session)
    ds.serve(FRAME)
    ds.close()
    ds.close()  # idempotent
    assert session.output_cache().pinned == 0
    with pytest.raises(RuntimeError, match="closed"):
        ds.serve(FRAME)


def test_delta_session_survives_external_cache_eviction():
    # a cache too small to hold even one frame's bands: every "clean"
    # band misses residency and is re-served — pure cost, still exact
    session = make_session(vertical_policy="zero")
    with DeltaSession(session, cache_bytes=1024) as ds:
        for frame in clip_with_motion(3):
            np.testing.assert_array_equal(
                ds.serve(frame), np.asarray(session.upscale(frame)))
    assert session.temporal_stats()["cover_violations"] == 0


# ----------------------------------------------------------------------
# stream(delta=True) + abandoned-stream cleanup
# ----------------------------------------------------------------------
def test_stream_delta_end_to_end():
    session = make_session(vertical_policy="halo")
    clip = clip_with_motion()
    with SRServer({"abpn": session}) as server:
        async def run():
            outs = []
            async for hr in server.stream(clip, delta=True):
                outs.append(hr)
            return outs

        outs = asyncio.run(run())
    refs = np.asarray(session.upscale(np.stack(clip)))
    assert len(outs) == len(clip)
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    t = session.temporal_stats()
    assert t["frames"] == len(clip) and t["bands_skipped"] > 0


@pytest.mark.parametrize("delta", [False, True])
def test_abandoned_stream_releases_resources(delta):
    """aclose() after one frame must leave no queued frames, no pinned
    carry buckets, and (delta) no pinned cache entries behind."""
    session = make_session(vertical_policy="halo")
    clip = [FRAME.copy() for _ in range(6)]
    with SRServer({"abpn": session}) as server:
        async def run():
            gen = server.stream(clip, delta=delta, lookahead=4)
            async for _ in gen:
                break  # abandon after the first frame
            await gen.aclose()

        asyncio.run(run())
        g = server.scheduler_stats()
        assert g["pending_frames"] == 0
        assert g["carry_buckets"] == 0
        assert g["inflight_dispatches"] == 0
    if delta:
        assert session.output_cache().pinned == 0


# ----------------------------------------------------------------------
# satellites: registry error, mesh parity (subprocess)
# ----------------------------------------------------------------------
def test_registry_unknown_model_lists_names_and_suggests():
    with pytest.raises(ValueError) as exc:
        get_sr_model("abpn-3x")
    msg = str(exc.value)
    assert "abpn_x3" in msg          # canonical names listed
    assert "abpn-x3" in msg          # aliases listed
    assert "did you mean 'abpn-x3'" in msg
    with pytest.raises(ValueError) as exc2:
        get_sr_model("totally_unknown")
    assert "registered" in str(exc2.value)


@pytest.mark.slow
def test_delta_parity_on_mesh_session_subprocess(subproc):
    """Delta serving on a band-sharded mesh session: partial dispatches
    run locally, the guarantee vs the SHARDED full path holds because
    sharded full re-upscale is itself bit-exact vs single-device."""
    out = subproc("""
        import jax, numpy as np
        from repro import engine
        from repro.engine.temporal import DeltaSession
        from repro.models.abpn import ABPNConfig, init_abpn

        layers = init_abpn(jax.random.PRNGKey(2), ABPNConfig())
        session = engine.SRSession(
            layers, backend="tilted", vertical_policy="halo",
            band_rows=6, mesh=(2, 2))
        rng = np.random.default_rng(7)
        base = rng.random((24, 16, 3), dtype=np.float32)
        moved = base.copy(); moved[12:14] += 0.25
        clip = [base, base.copy(), moved]
        exact = True
        with DeltaSession(session) as ds:
            for f in clip:
                exact &= np.array_equal(
                    ds.serve(f), np.asarray(session.upscale(f)))
        t = session.temporal_stats()
        assert t["bands_skipped"] > 0, t
        print("MESH_DELTA_OK", exact, t["reuse_ratio"])
    """)
    assert "MESH_DELTA_OK True" in out
