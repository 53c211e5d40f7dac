"""Batched engine: plan validation, batched == per-frame, halo exactness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.fusion import conv_stack_reference
from repro.models.abpn import (
    ABPNConfig,
    apply_abpn,
    depth_to_space,
    init_abpn,
    make_anchor,
)

CFG = ABPNConfig()
LAYERS = init_abpn(jax.random.PRNGKey(2), CFG)
FRAMES = jax.random.uniform(jax.random.PRNGKey(3), (3, 120, 64, 3))


# ----------------------------------------------------------------------
# SRPlan validation
# ----------------------------------------------------------------------
def test_plan_validates_geometry():
    with pytest.raises(ValueError):  # height not a band multiple
        engine.SRPlan(height=100, width=64, band_rows=60)
    with pytest.raises(ValueError):  # tile_cols below the overlap hand-off
        engine.SRPlan(height=120, width=64, tile_cols=1)
    with pytest.raises(ValueError):
        engine.SRPlan(height=120, width=64, band_rows=-60)
    with pytest.raises(ValueError):
        engine.SRPlan(height=0, width=64)


def test_plan_validates_enums():
    with pytest.raises(ValueError):
        engine.SRPlan(height=120, width=64, backend="magic")
    with pytest.raises(ValueError):
        engine.SRPlan(height=120, width=64, vertical_policy="mirror")
    with pytest.raises(ValueError):
        engine.SRPlan(height=120, width=64, precision="fp8")


def test_plan_kernel_accepts_every_policy_and_precision():
    """The Pallas backend covers the full plan space (no zero-only carve-out)."""
    for policy in engine.VERTICAL_POLICIES:
        for precision in engine.PRECISIONS:
            plan = engine.SRPlan(height=120, width=64, backend="kernel",
                                 vertical_policy=policy, precision=precision)
            assert (plan.vertical_policy, plan.precision) == (policy, precision)


def test_plan_checks_layer_channels():
    with pytest.raises(ValueError):
        engine.make_plan(LAYERS, (120, 64, 4))


def test_make_plan_rejects_empty_layer_stack():
    with pytest.raises(ValueError, match="layer stack is empty"):
        engine.make_plan([], (120, 64, 3))


def test_plan_derived_geometry_and_invariants():
    plan = engine.make_plan(LAYERS, (120, 64, 3), band_rows=60, tile_cols=8)
    assert plan.num_bands == 2
    assert plan.num_layers == 7
    assert plan.schedule.num_tiles == (64 + 6 + 7) // 8
    assert plan.hr_shape == (360, 192, 3)
    plan.check_invariants()  # full tile/layer hand-off sweep


@pytest.mark.parametrize("width", [640, 1280])
def test_tilted_default_is_one_tile_per_band(width):
    """Off the kernel backend the tile is derived from the width: the
    whole tilted sweep, W + L - 1 columns rounded up to 8, in one tile."""
    plans = [
        engine.SRPlan(height=360, width=width),
        engine.SRPlan.from_request((360, width, 3), num_layers=7),
        engine.make_plan(LAYERS, (360, width, 3)),
        engine.SRSession(LAYERS, autotune="off").plan_for((360, width, 3)),
    ]
    for plan in plans:
        assert plan.tile_cols == -(-(width + 6) // 8) * 8
        assert plan.tile_cols % 8 == 0
        assert plan.schedule.num_tiles == 1
        plan.check_invariants()
    assert engine.derive_tile_cols("tilted", width, 7) == plans[0].tile_cols


def test_band_sharded_shard_plan_is_one_tile():
    """Each shard of the 4K band-sharded plan (3 bands of 180 x 1280 rows
    and columns) sweeps its bands in one tile."""
    from repro.engine.sharding import MeshSpec, ShardedPlan

    splan = ShardedPlan(
        engine.SRPlan.from_request((720, 1280, 3), num_layers=7),
        MeshSpec(replicas=1, band_shards=4),
    )
    local = splan.local_plan
    assert (local.height, local.width, local.num_bands) == (180, 1280, 3)
    assert local.tile_cols == splan.plan.tile_cols == 1288
    assert local.schedule.num_tiles == 1


@pytest.mark.parametrize("width", [64, 640, 1280])
def test_kernel_backend_keeps_eight_column_tiles(width):
    """The kernel's overlap queue is VMEM scratch sized by the tile."""
    assert engine.SRPlan(height=360, width=width, backend="kernel").tile_cols == 8
    plan = engine.SRPlan.from_request((360, width, 3), num_layers=7,
                                      backend="kernel")
    assert plan.tile_cols == 8
    assert plan.schedule.num_tiles == -(-(width + 6) // 8)
    session = engine.SRSession(LAYERS, backend="kernel", autotune="off")
    assert session.plan_for((360, width, 3)).tile_cols == 8


@pytest.mark.parametrize("backend", ["tilted", "kernel"])
def test_explicit_tile_cols_is_kept(backend):
    assert engine.SRPlan(height=120, width=64, tile_cols=16,
                         backend=backend).tile_cols == 16
    plan = engine.SRPlan.from_request((120, 64, 3), num_layers=7,
                                      tile_cols=4, backend=backend)
    assert (plan.tile_cols, plan.schedule.num_tiles) == (4, 18)
    assert engine.make_plan(LAYERS, (120, 64, 3), tile_cols=8,
                            backend=backend).tile_cols == 8
    session = engine.SRSession(LAYERS, backend=backend, tile_cols=12,
                               autotune="off")
    assert session.plan_for((120, 64, 3)).tile_cols == 12


# ----------------------------------------------------------------------
# Batched engine == per-frame legacy shim, all backends
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend,policy", [
    ("reference", "zero"),
    ("tilted", "zero"),
    ("tilted", "halo"),
    ("tilted", "replicate"),
    pytest.param("kernel", "zero", marks=pytest.mark.slow),
    pytest.param("kernel", "halo", marks=pytest.mark.slow),
    pytest.param("kernel", "replicate", marks=pytest.mark.slow),
])
def test_batched_equals_per_frame(backend, policy):
    plan = engine.make_plan(LAYERS, FRAMES.shape[1:], band_rows=60,
                            vertical_policy=policy, backend=backend)
    batched = engine.run(plan, LAYERS, FRAMES)
    assert batched.shape == (3, 360, 192, 3)
    for i in range(FRAMES.shape[0]):
        single = apply_abpn(LAYERS, FRAMES[i], CFG, method=backend,
                            band_rows=60, vertical_policy=policy)
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(single))


@pytest.mark.slow
def test_batch_of_8_single_call_per_backend():
    """Acceptance: 8 frames through one jitted engine call per backend."""
    frames = jax.random.uniform(jax.random.PRNGKey(9), (8, 60, 32, 3))
    outs = {}
    for backend in engine.BACKENDS:
        plan = engine.make_plan(LAYERS, frames.shape[1:], band_rows=30,
                                backend=backend)
        fn = engine.build_executor(plan, LAYERS)
        outs[backend] = np.asarray(fn(frames))  # one call, whole batch
        assert outs[backend].shape == (8, 180, 96, 3)
    # tilted and kernel share the zero band policy -> near-identical
    np.testing.assert_allclose(outs["tilted"], outs["kernel"],
                               atol=5e-4, rtol=0)


# ----------------------------------------------------------------------
# Halo exactness via the plan API
# ----------------------------------------------------------------------
def test_halo_features_bit_exact_with_reference():
    plan = engine.make_plan(LAYERS, FRAMES.shape[1:], band_rows=60,
                            vertical_policy="halo", backend="tilted")
    feats = engine.sr_features(plan, LAYERS, FRAMES)
    for i in range(FRAMES.shape[0]):
        ref = conv_stack_reference(FRAMES[i], LAYERS)
        np.testing.assert_array_equal(np.asarray(feats[i]), np.asarray(ref))


def test_halo_single_band_image():
    """Halo margins past both image edges (1-band frame) stay exact."""
    frames = jax.random.uniform(jax.random.PRNGKey(4), (2, 60, 40, 3))
    plan = engine.make_plan(LAYERS, frames.shape[1:], band_rows=60,
                            vertical_policy="halo", backend="tilted")
    feats = engine.sr_features(plan, LAYERS, frames)
    for i in range(2):
        ref = conv_stack_reference(frames[i], LAYERS)
        np.testing.assert_array_equal(np.asarray(feats[i]), np.asarray(ref))


# ----------------------------------------------------------------------
# HR epilogue
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("clip", [True, False], ids=["clip", "noclip"])
@pytest.mark.parametrize("width", [21, 128])
@pytest.mark.parametrize("scale", [2, 3, 4])
def test_sr_epilogue_bitwise_depth_to_space(scale, width, clip, dtype):
    """The lane-dense epilogue is bitwise the model's definition: anchor add,
    ``depth_to_space``, clip, cast — NaN, -0.0 and out-of-range values
    included."""
    n, h, c = 2, 5, 3
    rng = np.random.default_rng(scale * 1000 + width)
    x = rng.uniform(-0.5, 1.5, (n, h, width, c)).astype(np.float32)
    feats = rng.normal(0.0, 1.0, (n, h, width, c * scale * scale)).astype(np.float32)
    x[:, 0], feats[:, 0] = -0.0, -0.0  # -0.0 + -0.0 stays -0.0 unclipped
    x[:, 1, ::3] = np.nan
    feats[:, 2, ::5] = np.nan
    x, feats = jnp.asarray(x, dtype), jnp.asarray(feats, dtype)
    plan = engine.SRPlan(height=h, width=width, band_rows=h, scale=scale, clip=clip)

    got = jax.jit(lambda a, f: engine.sr_epilogue(plan, a, f, jnp.float32))(x, feats)

    def oracle(a, f):
        hr = jax.vmap(lambda o: depth_to_space(o, scale))(f + make_anchor(a, scale))
        return (jnp.clip(hr, 0.0, 1.0) if clip else hr).astype(jnp.float32)

    want = jax.jit(oracle)(x, feats)
    assert got.shape == want.shape == (n, h * scale, width * scale, c)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


# ----------------------------------------------------------------------
# Numerics policies
# ----------------------------------------------------------------------
def test_precision_int8_stays_close():
    plan32 = engine.make_plan(LAYERS, FRAMES.shape[1:], backend="tilted")
    plan8 = engine.make_plan(LAYERS, FRAMES.shape[1:], backend="tilted",
                             precision="int8")
    hr32 = engine.run(plan32, LAYERS, FRAMES)
    hr8 = engine.run(plan8, LAYERS, FRAMES)
    mse = float(jnp.mean((hr32 - hr8) ** 2))
    psnr = 10 * np.log10(1.0 / max(mse, 1e-12))
    assert psnr > 40.0


def test_precision_bf16_runs_and_tracks_fp32():
    plan = engine.make_plan(LAYERS, FRAMES.shape[1:], backend="tilted",
                            precision="bf16")
    hr = engine.run(plan, LAYERS, FRAMES)
    assert hr.dtype == FRAMES.dtype  # cast back at the boundary
    ref = engine.run(
        engine.make_plan(LAYERS, FRAMES.shape[1:], backend="tilted"),
        LAYERS, FRAMES)
    assert float(jnp.max(jnp.abs(hr - ref))) < 0.1


# ----------------------------------------------------------------------
# VideoStream driver
# ----------------------------------------------------------------------
def test_video_stream_serves_and_reports():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30,
                            backend="tilted")
    stream = engine.VideoStream(plan, LAYERS, batch_size=2)
    compile_s = stream.warmup()
    assert compile_s > 0
    frames = jax.random.uniform(jax.random.PRNGKey(5), (6, 60, 32, 3))
    hr = stream.run(frames)
    assert hr.shape == (6, 180, 96, 3)
    s = stream.stats()
    assert s["frames"] == 6 and s["batches"] == 3
    assert s["fps"] > 0 and s["p95_ms"] >= s["p50_ms"] > 0
    # streamed result == one-shot batch through the same plan
    np.testing.assert_array_equal(
        np.asarray(hr), np.asarray(engine.run(plan, LAYERS, frames)))


def test_video_stream_rejects_wrong_batch():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30)
    stream = engine.VideoStream(plan, LAYERS, batch_size=2)
    with pytest.raises(ValueError):
        stream.process(jnp.zeros((3, 60, 32, 3)))
    with pytest.raises(ValueError):  # real_frames outside the batch
        stream.process(jnp.zeros((2, 60, 32, 3)), real_frames=3)


def test_video_stream_ragged_tail():
    """A clip that is not a batch multiple serves without recompilation:
    the tail batch is padded, the output trimmed, stats count real frames."""
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30,
                            backend="tilted")
    stream = engine.VideoStream(plan, LAYERS, batch_size=4)
    stream.warmup()
    frames = jax.random.uniform(jax.random.PRNGKey(7), (7, 60, 32, 3))
    hr = stream.run(frames)
    assert hr.shape == (7, 180, 96, 3)
    s = stream.stats()
    assert s["frames"] == 7 and s["batches"] == 2  # 4 + 3(padded to 4)
    # output equals frame-by-frame execution through the same plan
    np.testing.assert_array_equal(
        np.asarray(hr), np.asarray(engine.run(plan, LAYERS, frames)))


def test_video_stream_empty_clip_and_degenerate_stats():
    plan = engine.make_plan(LAYERS, (60, 32, 3), band_rows=30)
    stream = engine.VideoStream(plan, LAYERS, batch_size=2)
    hr = stream.run(jnp.zeros((0, 60, 32, 3)))
    assert hr.shape == (0, 180, 96, 3)
    s = stream.stats()
    assert s["frames"] == 0 and s["fps"] == 0.0
    # zero recorded latency (clock too coarse) must report 0.0, not inf
    stream._lat_ms.append(0.0)
    stream._frames += 2
    s = stream.stats()
    assert s["fps"] == 0.0 and np.isfinite(s["fps"])
