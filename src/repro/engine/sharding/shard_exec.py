"""Band-sharded executor: the tilted band loop under ``shard_map``.

Each device on the ``bands`` mesh axis owns a contiguous block of
``num_bands / band_shards`` whole bands (``H / S`` rows) of every frame.
For the ``zero``/``replicate`` vertical policies bands are independent and
the shards run with no communication at all.  For ``halo`` the only
cross-shard coupling is the L-row margin at the two shard edges; an
``lax.ppermute`` pulls the neighbour rows so that each shard can
reconstruct exactly the ``(R + 2L)``-row slabs ``core.fusion.halo_slabs``
would have cut from the zero-padded full frame:

  * a shard's extended rows ``concat([up, local, down])`` equal
    ``padded[s*H_local : s*H_local + H_local + 2L]`` of the L-zero-padded
    frame — ppermute leaves ZEROS on the edge shards that have no
    neighbour, which is exactly the global zero padding;
  * local band ``b``'s slab is ``ext[b*R : b*R + R + 2L]`` and its global
    valid-row bounds are the same clip formulas ``halo_slabs`` uses with
    the global band index ``axis_index('bands') * bands_per_shard + b``.

Bit-exactness vs the single-device executor therefore holds by
construction: identical slab values, identical per-band bounds, identical
band kernel (tilted vmap or Pallas), identical epilogue
(``executor.sr_epilogue``, row-block local).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.fusion import tilted_fused_band
from repro.distributed.partitioning import logical_to_spec, sr_rules
from repro.engine.executor import (
    PreparedStack,
    compute_dtype_for,
    sr_epilogue,
    sr_features,
)
from repro.engine.sharding.mesh_plan import ShardedPlan
from repro.launch.mesh import SR_BAND_AXIS

__all__ = [
    "build_sharded_executor",
    "frame_spec",
    "halo_exchange_bytes_per_frame",
]

# Logical axes of a frame batch (N, H, W, C) — resolved against SR_RULES.
FRAME_AXES = ("sr_batch", "sr_rows", "sr_cols", "sr_chan")


def frame_spec(mesh: jax.sharding.Mesh) -> P:
    """PartitionSpec for a frame batch on ``mesh`` (rows over ``bands``)."""
    return logical_to_spec(FRAME_AXES, mesh, sr_rules())


def halo_exchange_bytes_per_frame(plan, band_shards: int) -> int:
    """Bytes moved across shard edges per frame (both directions).

    ``zero``/``replicate`` shard without communication; ``halo`` exchanges
    the L-row margin at each of the ``S - 1`` internal edges, in both
    directions, in the compute dtype.
    """
    if band_shards <= 1 or plan.vertical_policy != "halo":
        return 0
    itemsize = jnp.dtype(compute_dtype_for(plan.precision)).itemsize
    edge_rows = plan.num_layers * plan.width * plan.in_channels
    return 2 * (band_shards - 1) * edge_rows * itemsize


def _halo_features_local(plan, local, stack: PreparedStack, x: jax.Array):
    """Per-shard halo-policy features: exchange, re-slab, run, crop.

    ``x`` is this shard's ``(N, H/S, W, C0)`` row block in compute dtype;
    returns ``(N, H/S, W, ChL)`` features identical to the matching rows of
    the single-device halo path.
    """
    N, Hl, W, C0 = x.shape
    R, L = plan.band_rows, plan.num_layers
    S = plan.height // Hl
    Bl = local.num_bands
    slab = R + 2 * L

    # Neighbour margins: shard 0 / shard S-1 receive zeros from ppermute on
    # their open edge — identical to the global L-row zero padding.
    fwd = [(i, i + 1) for i in range(S - 1)]
    bwd = [(i + 1, i) for i in range(S - 1)]
    up = jax.lax.ppermute(x[:, -L:], SR_BAND_AXIS, fwd)
    down = jax.lax.ppermute(x[:, :L], SR_BAND_AXIS, bwd)
    ext = jnp.concatenate([up, x, down], axis=1)  # padded[s*Hl : s*Hl+Hl+2L]

    slabs = jnp.stack([ext[:, b * R : b * R + slab] for b in range(Bl)], axis=1)
    slabs = slabs.reshape(N * Bl, slab, W, C0)

    # Global valid-row bounds, same clip formulas as halo_slabs but with the
    # global band index; flat order n*Bl + b matches the reshape above.
    g = jax.lax.axis_index(SR_BAND_AXIS) * Bl + jnp.arange(Bl, dtype=jnp.int32)
    lo = jnp.clip(L - g * R, 0, slab).astype(jnp.int32)
    hi = jnp.clip(L + plan.height - g * R, 0, slab).astype(jnp.int32)
    lo = jnp.tile(lo, N)
    hi = jnp.tile(hi, N)

    if plan.backend == "kernel":
        from repro.kernels import ops  # local import: kernels are optional

        out = ops._tilted_fused_bands(
            slabs,
            stack.packed,
            tile_cols=plan.tile_cols,
            add_anchor=False,
            anchor_repeats=plan.scale * plan.scale,
            interpret=ops.default_interpret(),
            row_policy="zero",
            row_bounds=jnp.stack([lo, hi], axis=1),
            compute_dtype=x.dtype,
        )
    else:
        out = jax.vmap(
            lambda band, l, h: tilted_fused_band(
                band, stack.layers, plan.tile_cols, row_pad="zero",
                row_valid=(l, h),
            )
        )(slabs, lo, hi)
    out = out[:, L : L + R]  # crop the recompute margin
    return out.reshape(N, Hl, W, out.shape[-1])


def _sharded_body(splan: ShardedPlan, stack: PreparedStack, frames: jax.Array):
    """The per-shard program shard_map maps over the ``bands`` axis."""
    plan = splan.plan
    local = splan.local_plan
    in_dtype = frames.dtype
    x = frames.astype(compute_dtype_for(plan.precision))
    if splan.spec.band_shards == 1 or plan.vertical_policy != "halo":
        # Bands are shard-local (or there is only one shard): the ordinary
        # backend over the local row block IS the global computation.
        feats = sr_features(local, stack.layers, x, packed=stack.packed)
    else:
        feats = _halo_features_local(plan, local, stack, x)
    return sr_epilogue(local, x, feats, in_dtype)


def build_sharded_executor(
    splan: ShardedPlan, stack: PreparedStack, mesh: jax.sharding.Mesh
):
    """Compile ``splan`` + ``stack`` into a mesh-sharded frame-batch callable.

    ``mesh`` must carry a ``bands`` axis of size ``spec.band_shards`` (a
    replica's :func:`repro.launch.mesh.band_submesh`, or any 1-D bands
    mesh).  The callable runs the jitted shard_map program and returns the
    HR batch with the same row sharding (gather with ``np.asarray`` when a
    host copy is needed).  Its input sharding is ``fn.in_sharding``: the
    server's assembler puts each slab straight into it, each chip
    receiving its own rows, so the callable's own ``device_put`` is a
    no-op for such a slab and only places one that arrives elsewhere (a
    device array, a caller's direct call).
    """
    spec = splan.spec
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis_sizes.get(SR_BAND_AXIS) != spec.band_shards:
        raise ValueError(
            f"mesh bands axis {axis_sizes.get(SR_BAND_AXIS)} != plan's "
            f"band_shards {spec.band_shards}"
        )
    fspec = frame_spec(mesh)
    body = functools.partial(_sharded_body, splan)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), fspec), out_specs=fspec,
        check_vma=False,
    )
    jitted = jax.jit(mapped)
    in_sharding = NamedSharding(mesh, fspec)

    def fn(frames):
        frames = jax.device_put(frames, in_sharding)
        return jitted(stack, frames)

    fn.jitted = jitted
    fn.in_sharding = in_sharding
    fn.donates_frames = False
    fn.mesh = mesh
    fn.sharded_plan = splan
    return fn
