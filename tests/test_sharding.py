"""Sharded serving: MeshSpec/ShardedPlan validation, shard-aware plan
verification, replica routing policy, and multi-device bit-exactness.

Pure-logic tests run anywhere.  Multi-device parity runs two ways: directly
in-process when the interpreter already sees >= 8 devices (the CI
tier1-multidevice job forces host devices via XLA_FLAGS), and in
subprocesses (``slow`` tier) so the full suite covers sharding even from a
single-device main process.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.plan_check import required_halo_margin, verify_plan
from repro.engine.plan import SRPlan, shardable_band_rows
from repro.engine.session import SRSession
from repro.engine.sharding import (
    MeshSpec,
    ReplicaRouter,
    ShardedPlan,
    build_sharded_executor,
    halo_exchange_bytes_per_frame,
)
from repro.engine.sharding.mesh_plan import check_shardable, ensure_shardable
from repro.engine.sharding.router import _Replica
from repro.models.abpn import ABPNConfig, init_abpn

CFG = ABPNConfig(num_layers=3, feature_channels=8)
LAYERS = init_abpn(jax.random.PRNGKey(0), CFG)


def small_plan(**kw):
    kw.setdefault("height", 24)
    kw.setdefault("width", 16)
    kw.setdefault("num_layers", 3)
    kw.setdefault("band_rows", 6)
    return SRPlan(**kw)


# ----------------------------------------------------------------------
# MeshSpec
# ----------------------------------------------------------------------
def test_mesh_spec_coerce():
    assert MeshSpec.coerce(None) == MeshSpec(1, 1)
    assert MeshSpec.coerce((2, 4)) == MeshSpec(replicas=2, band_shards=4)
    spec = MeshSpec(3, 2)
    assert MeshSpec.coerce(spec) is spec


def test_mesh_spec_properties():
    spec = MeshSpec(replicas=2, band_shards=4)
    assert spec.devices_needed == 8
    assert spec.descriptor == "2x4"
    assert not spec.is_trivial
    assert MeshSpec().is_trivial


def test_mesh_spec_rejects_bad_values():
    with pytest.raises(ValueError):
        MeshSpec(0, 1)
    with pytest.raises(ValueError):
        MeshSpec(1, -2)
    with pytest.raises(ValueError):
        MeshSpec.coerce("2x4")  # strings are not topologies
    with pytest.raises(ValueError):
        MeshSpec.coerce((1, 2, 3))


# ----------------------------------------------------------------------
# Shardability: check / ensure / ShardedPlan
# ----------------------------------------------------------------------
def test_check_shardable():
    assert check_shardable(small_plan(), 1) is None
    assert check_shardable(small_plan(), 2) is None  # 4 bands / 2 shards
    err = check_shardable(small_plan(backend="reference"), 2)
    assert err is not None and "reference" in err
    err = check_shardable(small_plan(band_rows=24), 2)  # 1 band, 2 shards
    assert err is not None and "split" in err


def test_ensure_shardable_rebands():
    plan = small_plan(height=48, band_rows=48)  # 1 band: not 2-shardable
    fixed = ensure_shardable(plan, MeshSpec(1, 2))
    assert fixed.band_rows == 24 and fixed.num_bands == 2
    assert fixed.height == plan.height
    ok = small_plan()
    assert ensure_shardable(ok, MeshSpec(1, 2)) is ok  # untouched when legal
    with pytest.raises(ValueError):
        ensure_shardable(small_plan(backend="reference"), MeshSpec(1, 2))
    with pytest.raises(ValueError):
        # prime height: only the full-height single band is legal
        ensure_shardable(SRPlan(height=97, width=16, num_layers=3,
                                band_rows=97), MeshSpec(1, 2))


def test_shardable_band_rows():
    assert shardable_band_rows(360, 3) == 60  # paper frame: 6 bands / 3
    assert shardable_band_rows(48, 2) == 24
    assert shardable_band_rows(97, 2) is None
    with pytest.raises(ValueError):
        shardable_band_rows(48, 0)


def test_sharded_plan_local_geometry():
    splan = ShardedPlan(plan=small_plan(), spec=MeshSpec(1, 2))
    assert splan.local_plan.height == 12
    assert splan.local_plan.band_rows == 6
    assert splan.bands_per_shard == 2
    trivial = ShardedPlan(plan=small_plan())
    assert trivial.local_plan is trivial.plan
    with pytest.raises(ValueError):
        ShardedPlan(plan=small_plan(band_rows=24), spec=MeshSpec(1, 2))
    with pytest.raises(ValueError):
        ShardedPlan(plan=small_plan(backend="reference"), spec=MeshSpec(1, 2))


# ----------------------------------------------------------------------
# Shard-aware static verification (plan_check satellite)
# ----------------------------------------------------------------------
def _shard_errors(findings):
    return [f for f in findings
            if f.rule.startswith("shard_") and f.severity == "error"]


def test_verify_plan_shard_halo_insufficiency_is_error():
    plan = small_plan(vertical_policy="halo")
    need = required_halo_margin(plan.num_layers)
    bad = verify_plan(plan, band_shards=2, shard_halo_margin=need - 1)
    errs = _shard_errors(bad)
    assert errs and errs[0].rule == "shard_halo_sufficiency"
    assert "shards=2" in errs[0].where
    # sufficient margin (the default, derived from the geometry) is clean
    good = verify_plan(plan, band_shards=2)
    assert not _shard_errors(good)


def test_verify_plan_shard_backend_and_alignment():
    ref = SRPlan(height=24, width=16, num_layers=3, backend="reference",
                 band_rows=24)
    errs = _shard_errors(verify_plan(ref, band_shards=2))
    assert errs and errs[0].rule == "shard_backend"
    one_band = small_plan(band_rows=24)
    errs = _shard_errors(verify_plan(one_band, band_shards=2))
    assert errs and errs[0].rule == "shard_band_alignment"


def test_verify_plan_unsharded_has_no_shard_findings():
    plan = small_plan(vertical_policy="halo")
    assert not [f for f in verify_plan(plan) if f.rule.startswith("shard_")]
    assert not [f for f in verify_plan(plan, band_shards=1)
                if f.rule.startswith("shard_")]


def test_sharded_plan_verify_threads_band_shards():
    splan = ShardedPlan(plan=small_plan(vertical_policy="halo"),
                        spec=MeshSpec(1, 2))
    assert not _shard_errors(splan.verify())
    errs = _shard_errors(splan.verify(shard_halo_margin=0))
    assert errs and errs[0].rule == "shard_halo_sufficiency"


# ----------------------------------------------------------------------
# Halo-exchange traffic model
# ----------------------------------------------------------------------
def test_halo_exchange_bytes_per_frame():
    plan = small_plan(vertical_policy="halo", width=32)
    # 2 directions * (S-1) edges * L rows * W * C0 * fp32
    assert halo_exchange_bytes_per_frame(plan, 2) == 2 * 1 * 3 * 32 * 3 * 4
    assert halo_exchange_bytes_per_frame(plan, 4) == 2 * 3 * 3 * 32 * 3 * 4
    assert halo_exchange_bytes_per_frame(plan, 1) == 0
    for policy in ("zero", "replicate"):
        p = small_plan(vertical_policy=policy, width=32)
        assert halo_exchange_bytes_per_frame(p, 4) == 0


# ----------------------------------------------------------------------
# Replica routing policy (host-side logic; no devices required)
# ----------------------------------------------------------------------
def _bare_router(policy, n):
    r = ReplicaRouter.__new__(ReplicaRouter)
    r.policy = policy
    r._replicas = [_Replica(index=i, mesh=None, cache=None, stacks={})
                   for i in range(n)]
    r._rr = 0
    return r


def test_round_robin_rotation():
    r = _bare_router("round_robin", 3)
    assert [r.pick() for _ in range(7)] == [0, 1, 2, 0, 1, 2, 0]


def test_least_loaded_prefers_idle_then_cold():
    r = _bare_router("least_loaded", 3)
    assert r.pick() == 0  # all equal: lowest index
    r.note_launch(0)
    assert r.pick() == 1  # 0 has one in flight
    r.note_launch(1)
    assert r.pick() == 2
    r.note_launch(2)
    r.note_complete(1)  # 1 drains first: equal inflight broken by history?
    # inflight: [1, 0, 1] -> replica 1
    assert r.pick() == 1
    r.note_complete(0)
    r.note_complete(2)
    # all idle again; dispatch history [1, 1, 1] ties -> lowest index
    assert r.pick() == 0


def test_note_complete_floors_at_zero():
    r = _bare_router("least_loaded", 2)
    r.note_complete(0)
    assert r._replicas[0].inflight == 0


def test_replica_fill():
    r = _bare_router("round_robin", 2)
    assert r.replica_fill() == 0.0  # no traffic yet
    r.note_launch(0)
    r.note_launch(1)
    assert r.replica_fill() == 1.0
    r.note_launch(0)
    r.note_launch(0)
    assert r.replica_fill() == pytest.approx(2 / 3)  # mean 2 / peak 3


def test_router_rejects_unknown_policy():
    with pytest.raises(ValueError):
        ReplicaRouter(None, MeshSpec(1, 1), policy="random")


# ----------------------------------------------------------------------
# Session-level mesh validation (topology-independent paths)
# ----------------------------------------------------------------------
def test_session_trivial_mesh_is_unsharded():
    s = SRSession(LAYERS, mesh=(1, 1))
    assert s.mesh_spec is None and s._router is None
    assert s.sharding_stats() is None


def test_session_rejects_bogus_mesh():
    with pytest.raises(ValueError):
        SRSession(LAYERS, mesh="2x4")


@pytest.mark.skipif(jax.device_count() != 1,
                    reason="needs a single-device interpreter")
def test_session_mesh_needs_devices():
    with pytest.raises(ValueError, match="devices"):
        SRSession(LAYERS, mesh=(1, 2))


# ----------------------------------------------------------------------
# Multi-device parity, in-process (runs under CI tier1-multidevice, where
# XLA_FLAGS forces 8 host devices before jax initialises)
# ----------------------------------------------------------------------
needs_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs >= 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@needs_devices
@pytest.mark.parametrize("backend", ["tilted", "kernel"])
@pytest.mark.parametrize("policy", ["zero", "halo", "replicate"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_executor_bit_exact(backend, policy, shards):
    from repro.engine.executor import build_stack_executor, prepare_stack
    from repro.launch.mesh import band_submesh, make_sr_mesh

    plan = small_plan(vertical_policy=policy, backend=backend)
    stack = prepare_stack(plan, LAYERS)
    frames = jax.random.uniform(jax.random.PRNGKey(7),
                                (2, *plan.lr_shape), jnp.float32)
    ref = build_stack_executor(plan, stack)(frames)
    mesh = band_submesh(make_sr_mesh(1, shards), 0)
    fn = build_sharded_executor(
        ShardedPlan(plan=plan, spec=MeshSpec(1, shards)), stack, mesh)
    out = fn(frames)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@needs_devices
def test_sharded_executor_rejects_mismatched_mesh():
    from repro.engine.executor import prepare_stack
    from repro.launch.mesh import band_submesh, make_sr_mesh

    plan = small_plan()
    stack = prepare_stack(plan, LAYERS)
    mesh2 = band_submesh(make_sr_mesh(1, 2), 0)
    with pytest.raises(ValueError, match="band_shards"):
        build_sharded_executor(
            ShardedPlan(plan=plan, spec=MeshSpec(1, 4)), stack, mesh2)


@needs_devices
def test_session_serving_bit_exact_and_routed():
    base = SRSession(LAYERS, vertical_policy="halo")
    sharded = SRSession(LAYERS, vertical_policy="halo",
                        mesh=(2, 4), route="round_robin")
    frames = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(3), (2, 48, 16, 3), jnp.float32))
    want = np.asarray(base.upscale(frames))
    for _ in range(4):  # sequential: each call is its own routed dispatch
        got = np.asarray(sharded.upscale(frames))
        np.testing.assert_array_equal(got, want)
    stats = sharded.sharding_stats()
    assert stats["mesh"] == "2x4" and stats["devices"] == 8
    assert sum(r["dispatches"] for r in stats["replicas"]) >= 4
    assert all(r["dispatches"] >= 1 for r in stats["replicas"])  # rotated
    assert stats["replica_fill"] > 0.0
    assert stats["halo_bytes_per_frame"] > 0


@needs_devices
def test_session_auto_rebands_for_mesh():
    # height 48 defaults to one 48-row band; 2 band shards force 24.
    # halo policy so the re-banded output stays bit-identical (zero /
    # replicate boundaries legitimately depend on where the bands fall).
    s = SRSession(LAYERS, vertical_policy="halo", mesh=(1, 2))
    plan = s.plan_for((48, 16, 3))
    assert plan.num_bands % 2 == 0
    base = SRSession(LAYERS, vertical_policy="halo")
    frames = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(9), (1, 48, 16, 3), jnp.float32))
    np.testing.assert_array_equal(np.asarray(s.upscale(frames)),
                                  np.asarray(base.upscale(frames)))


@needs_devices
def test_session_rejects_unshardable_explicit_band_rows():
    s = SRSession(LAYERS, mesh=(1, 2), band_rows=48)
    with pytest.raises(ValueError):
        s.plan_for((48, 16, 3))


# ----------------------------------------------------------------------
# Subprocess coverage (slow tier): the same guarantees from a
# single-device main process, via forced host devices
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_sharded_parity_subprocess(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.engine.executor import build_stack_executor, prepare_stack
        from repro.engine.plan import SRPlan
        from repro.engine.sharding import (MeshSpec, ShardedPlan,
                                           build_sharded_executor)
        from repro.launch.mesh import band_submesh, make_sr_mesh
        from repro.models.abpn import ABPNConfig, init_abpn

        layers = init_abpn(jax.random.PRNGKey(0),
                           ABPNConfig(num_layers=3, feature_channels=8))
        frames = jax.random.uniform(jax.random.PRNGKey(7), (2, 24, 16, 3))
        for backend in ("tilted", "kernel"):
            for policy in ("zero", "halo", "replicate"):
                plan = SRPlan(height=24, width=16, num_layers=3, band_rows=6,
                              vertical_policy=policy, backend=backend)
                stack = prepare_stack(plan, layers)
                ref = np.asarray(build_stack_executor(plan, stack)(frames))
                for S in (2, 4):
                    mesh = band_submesh(make_sr_mesh(1, S), 0)
                    fn = build_sharded_executor(
                        ShardedPlan(plan=plan, spec=MeshSpec(1, S)),
                        stack, mesh)
                    np.testing.assert_array_equal(np.asarray(fn(frames)), ref)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_replica_routing_subprocess(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.engine.session import SRSession
        from repro.models.abpn import ABPNConfig, init_abpn

        layers = init_abpn(jax.random.PRNGKey(0),
                           ABPNConfig(num_layers=3, feature_channels=8))
        base = SRSession(layers, vertical_policy="halo")
        sharded = SRSession(layers, vertical_policy="halo",
                            mesh=(2, 2), route="least_loaded")
        frames = np.asarray(jax.random.uniform(
            jax.random.PRNGKey(3), (2, 24, 16, 3), jnp.float32))
        want = np.asarray(base.upscale(frames))
        for _ in range(4):
            np.testing.assert_array_equal(
                np.asarray(sharded.upscale(frames)), want)
        stats = sharded.sharding_stats()
        assert stats["mesh"] == "2x2", stats
        assert sum(r["dispatches"] for r in stats["replicas"]) >= 4, stats
        print("OK")
    """)
    assert "OK" in out
