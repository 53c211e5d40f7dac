"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run pins the device count via XLA_FLAGS
before any jax initialisation).

Mesh shapes (TPU v5e pods):
  single pod : (data=16, model=16)           = 256 chips
  multi-pod  : (pod=2, data=16, model=16)    = 512 chips
"""

from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_mesh",
    "make_sr_mesh",
    "band_submesh",
    "SINGLE_POD",
    "MULTI_POD",
    "SR_REPLICA_AXIS",
    "SR_BAND_AXIS",
]

SINGLE_POD = ((16, 16), ("data", "model"))
MULTI_POD = ((2, 16, 16), ("pod", "data", "model"))

# SR serving mesh axes: ``replica`` is pure data parallelism (whole frames,
# no communication), ``bands`` splits each frame's row bands spatially
# (L-row halo exchange at shard edges).
SR_REPLICA_AXIS = "replica"
SR_BAND_AXIS = "bands"


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape, axes = MULTI_POD if multi_pod else SINGLE_POD
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests use small ones, e.g. (2, 4) on 8 host devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_sr_mesh(replicas: int, band_shards: int) -> jax.sharding.Mesh:
    """The serving mesh: ``(replica=R, bands=S)`` over ``R*S`` devices.

    On CPU, force enough host devices before jax initialises:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    if replicas <= 0 or band_shards <= 0:
        raise ValueError(
            f"mesh axes must be positive, got replicas={replicas} "
            f"band_shards={band_shards}"
        )
    needed = replicas * band_shards
    avail = jax.device_count()
    if needed > avail:
        raise ValueError(
            f"mesh ({replicas}x{band_shards}) needs {needed} devices but "
            f"only {avail} are visible; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return make_mesh((replicas, band_shards), (SR_REPLICA_AXIS, SR_BAND_AXIS))


def band_submesh(mesh: jax.sharding.Mesh, replica: int) -> jax.sharding.Mesh:
    """One replica's 1-D ``bands`` slice of an SR mesh.

    Each replica compiles and runs its own band-sharded executor over this
    submesh — the ``replica`` axis never appears inside a compiled program
    (replication is pure request routing, handled by ``ReplicaRouter``).
    """
    names = mesh.axis_names
    if names[-1] != SR_BAND_AXIS or SR_REPLICA_AXIS not in names:
        raise ValueError(f"not an SR mesh (axes {names})")
    rep_dim = names.index(SR_REPLICA_AXIS)
    devices = mesh.devices.take(indices=replica, axis=rep_dim)
    return jax.sharding.Mesh(devices, (SR_BAND_AXIS,))
