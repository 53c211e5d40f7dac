"""Batched plan executor — one jitted call per frame batch, any backend.

This replaces both the string dispatch in the legacy ``apply_abpn`` and the
per-band Python loop in ``core.fusion.run_banded``:

* ``reference`` — the full-image layerwise oracle, ``vmap``-ed over frames.
* ``tilted``    — the pure-JAX tilted sweep.  Frames are reshaped to a flat
  ``(N * num_bands, R, W, C)`` band axis and the band dimension is folded
  into a single ``vmap`` (bands of a frame are independent under every
  vertical policy, including ``halo`` where each band carries its own
  recompute margin), so the whole batch traces to one XLA computation with
  no Python-level banding.
* ``kernel``    — the Pallas datapath; the same flat band axis becomes the
  kernel's sequential grid dimension (``kernels.ops.tilted_fused_frames``),
  so a batch of frames is ONE ``pallas_call``.

All backends share the anchor + pixel-shuffle epilogue and the plan's
numerics policy (fp32 / bf16 / int8 dequant-on-read weights).

Weight preparation (the numerics policy + the kernel's pad/pack) has two
homes:

* :func:`prepare_stack` builds a device-resident :class:`PreparedStack`
  ONCE per weight stack; :func:`build_stack_executor` compiles a serving
  executor that takes the stack as a plain pytree argument — so the int8
  quantise round-trip and the kernel's weight scatter never execute inside
  the per-batch jitted call.  This is what ``SRSession`` serves through.
* :func:`run`/:func:`build_executor` keep the self-contained signature
  (raw float layers in, preparation traced into the call) — the
  differentiable path QAT training uses, and the oracle the prepared path
  is tested bit-exact against.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fusion import (
    ConvLayer,
    conv_stack_reference,
    halo_slabs,
    tilted_fused_band,
)
from repro.core.quant import dequantize_layers, quantize_layers
from repro.engine.plan import SRPlan

__all__ = [
    "prepare_layers",
    "prepare_stack",
    "PreparedStack",
    "build_executor",
    "build_stack_executor",
    "build_band_executor",
    "executor_artifacts",
    "output_spec",
    "run",
    "sr_epilogue",
    "sr_features",
]

def prepare_layers(layers: Sequence[ConvLayer], precision: str) -> List[ConvLayer]:
    """Apply the plan's numerics policy to a float conv stack.

    ``fp32`` passes through; ``bf16`` casts weights/biases (activations are
    cast at the executor boundary); ``int8`` round-trips the weights through
    symmetric per-channel quantisation — the accelerator's storage format —
    and computes in fp32 (dequant-on-read).
    """
    if precision == "fp32":
        return list(layers)
    if precision == "bf16":
        return [
            ConvLayer(
                w=l.w.astype(jnp.bfloat16), b=l.b.astype(jnp.bfloat16), relu=l.relu
            )
            for l in layers
        ]
    if precision == "int8":
        return dequantize_layers(quantize_layers(layers))
    raise ValueError(f"unknown precision {precision!r}")


@dataclasses.dataclass
class PreparedStack:
    """A weight stack with the plan's numerics + backend packing applied.

    Built ONCE per (weight stack, precision, backend) by
    :func:`prepare_stack`; the arrays are ordinary device-resident
    ``jax.Array``s, and the whole object is a pytree, so a jitted executor
    takes it as a plain argument — weight preparation never re-executes
    inside the per-batch call.  ``packed`` is only populated for the
    ``kernel`` backend (the Pallas launch's padded storage form).
    """

    layers: tuple  # Tuple[ConvLayer, ...], numerics applied
    packed: Optional[object]  # kernels.ops.PackedLayers | None
    precision: str
    backend: str

    def nbytes(self) -> int:
        """Device bytes this stack holds (prepared + packed forms)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves((self.layers, self.packed))
            if hasattr(leaf, "nbytes")
        )


jax.tree_util.register_dataclass(
    PreparedStack,
    data_fields=["layers", "packed"],
    meta_fields=["precision", "backend"],
)


def compute_dtype_for(precision: str):
    """The on-chip compute dtype a precision policy implies (int8 stores
    quantised weights but computes dequantised in fp32)."""
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def prepare_stack(plan: SRPlan, layers: Sequence[ConvLayer]) -> PreparedStack:
    """Apply ``plan``'s numerics policy — and, for the ``kernel`` backend,
    the launch's weight pad/pack — producing a device-resident
    :class:`PreparedStack`.

    Called eagerly this executes the int8 quantise round-trip / bf16 cast /
    kernel pack exactly once; the returned arrays are then reused by every
    batch served through :func:`build_stack_executor`.  The function is
    pure jnp, so it also traces cleanly when invoked inside a jit (the
    legacy self-contained path) or under ``grad`` (QAT).
    """
    prepared = tuple(prepare_layers(layers, plan.precision))
    packed = None
    if plan.backend == "kernel":
        from repro.kernels import ops  # local import: kernels are optional

        packed = ops.pack_stack(prepared, dtype=compute_dtype_for(plan.precision))
    return PreparedStack(
        layers=prepared,
        packed=packed,
        precision=plan.precision,
        backend=plan.backend,
    )


# ----------------------------------------------------------------------
# Backend feature executors: (N, H, W, C0) -> (N, H, W, ChL)
# ----------------------------------------------------------------------
def _features_reference(plan: SRPlan, layers, frames: jax.Array) -> jax.Array:
    return jax.vmap(lambda im: conv_stack_reference(im, layers))(frames)


def _features_tilted(plan: SRPlan, layers, frames: jax.Array) -> jax.Array:
    N, H, W, C0 = frames.shape
    R, L = plan.band_rows, plan.num_layers
    B = plan.num_bands
    policy = plan.vertical_policy

    if policy in ("zero", "replicate"):
        bands = frames.reshape(N * B, R, W, C0)
        out = jax.vmap(
            lambda band: tilted_fused_band(
                band, layers, plan.tile_cols, row_pad=policy
            )
        )(bands)
        return out.reshape(N, H, W, out.shape[-1])

    # halo: every band is the (R + 2L)-row slab of the zero-padded frame
    # starting at its own row offset; rows outside the real image are
    # phantom and masked per-layer via row_valid (exactly run_banded's
    # semantics, but uniform across bands so the band axis vmaps).  The
    # slab/bounds geometry is shared with the Pallas marshalling
    # (core.fusion.halo_slabs — the one definition of halo).
    slabs, bounds = halo_slabs(frames, R, L)
    out = jax.vmap(
        lambda band, l, h: tilted_fused_band(
            band, layers, plan.tile_cols, row_pad="zero", row_valid=(l, h)
        )
    )(slabs, bounds[:, 0], bounds[:, 1])
    out = out[:, L : L + R]  # crop the recompute margin
    return out.reshape(N, H, W, out.shape[-1])


def _features_kernel(
    plan: SRPlan, layers, frames: jax.Array, packed=None
) -> jax.Array:
    from repro.kernels import ops  # local import: kernels are optional

    # The kernel covers the full plan space: zero/replicate run the bands
    # directly with the matching in-kernel row padding, halo marshals
    # (R+2L)-row slabs with per-band valid-row bounds, and bf16 plans
    # compute in bf16 on-chip (frames arrive already cast, so the compute
    # dtype rides in on the input dtype).  ``packed`` (from a
    # PreparedStack) skips the per-call weight pad/scatter.
    return ops.tilted_fused_frames(
        frames,
        layers,
        band_rows=plan.band_rows,
        tile_cols=plan.tile_cols,
        vertical_policy=plan.vertical_policy,
        compute_dtype=frames.dtype,
        packed=packed,
    )


_BACKENDS = {
    "reference": _features_reference,
    "tilted": _features_tilted,
}


def sr_features(plan: SRPlan, layers, frames: jax.Array, packed=None) -> jax.Array:
    """Run the plan's conv-stack backend over a frame batch (no epilogue).

    ``layers`` are assumed already numerics-prepared; ``packed`` (kernel
    backend only) supplies pre-packed launch weights.  Its operations
    carry the ``sr_features`` scope in their HLO metadata, which names
    them in a profiler trace.
    """
    with jax.named_scope("sr_features"):
        if plan.backend == "kernel":
            return _features_kernel(plan, layers, frames, packed)
        return _BACKENDS[plan.backend](plan, layers, frames)


def _execute_stack(
    plan: SRPlan, stack: PreparedStack, frames: jax.Array
) -> jax.Array:
    """The per-batch computation over an already-prepared weight stack.

    This is what serving compiles: weight preparation happened when the
    :class:`PreparedStack` was built, so the jitted program contains ONLY
    the conv datapath + epilogue — no quantise round-trip, no kernel weight
    scatter (enforced by the ``repro.analysis.program_audit`` hot-path
    pass, which CI runs over every cached executor).
    """
    if frames.ndim != 4:
        raise ValueError(
            f"expected a frame batch (N, H, W, C), got shape {frames.shape}"
        )
    in_dtype = frames.dtype
    x = frames.astype(compute_dtype_for(plan.precision))
    feats = sr_features(plan, stack.layers, x, packed=stack.packed)
    return sr_epilogue(plan, x, feats, in_dtype)


def sr_epilogue(
    plan: SRPlan, x: jax.Array, feats: jax.Array, in_dtype
) -> jax.Array:
    """ABPN's residual epilogue: anchor add, pixel shuffle, clip, cast.

    Bitwise ``clip(vmap(depth_to_space)(feats + make_anchor(x, s)))`` (the
    convention of ``models.abpn``), laid out for the chip.  The HR batch is
    planar on a TPU (physically N, C, H, W), so every intermediate keeps a
    pixel axis minor: the anchor add and the clip run on (N, C, dy, dx, H, W),
    the rows interleave while W is minor, and the columns interleave by a
    transpose that puts (W, dx) above a minor sH and one back.  A minor
    sub-pixel or colour axis (size ``s`` or C) would be padded to 128 lanes.
    Pure data movement around the same add and clip, so NaN and -0.0 pass
    through as before (a one-hot matmul interleave would spread NaNs).

    Shared between the single-device executor, the partial-band one and the
    band-sharded one, whose bit-exactness rests on it.  Row-block local: LR
    row ``y`` maps to HR rows ``[y*s, y*s+s)``, so the epilogue can run
    independently on each row shard.  Its operations carry the
    ``sr_epilogue`` scope in their HLO metadata.
    """
    N, H, W, C = x.shape
    s = plan.scale
    with jax.named_scope("sr_epilogue"):
        # feats channel c*s*s + dy*s + dx -> (N, C, dy, dx, H, W)
        f = feats.reshape(N, H, W, C, s, s).transpose(0, 3, 4, 5, 1, 2)
        out = f + x.transpose(0, 3, 1, 2)[:, :, None, None]
        if plan.clip:
            out = jnp.clip(out, 0.0, 1.0)
        # rows: (N, C, dx, H, dy, W) -> (N, C, dx, sH, W)
        out = out.transpose(0, 1, 3, 4, 2, 5).reshape(N, C, s, H * s, W)
        # columns: (N, C, W, dx, sH) -> (N, C, sW, sH)
        out = out.transpose(0, 1, 4, 2, 3).reshape(N, C, W * s, H * s)
        hr = out.transpose(0, 3, 2, 1)  # (N, sH, sW, C), planar on the chip
        return hr.astype(in_dtype)


def _execute(plan: SRPlan, layers, frames: jax.Array) -> jax.Array:
    """The pure engine computation: ``(plan, layers, frames) -> HR batch``.

    Layers are a pytree ARGUMENT (not a closure), so this traces cleanly
    under ``grad``/``vmap`` (e.g. the QAT training example differentiates
    through it) and one jit cache entry serves every weight stack of the
    same structure.  Weight preparation is traced INTO the call here — the
    serving path avoids that via :func:`prepare_stack` +
    :func:`build_stack_executor`, which produce bit-identical results (the
    same preparation ops run on the same values, merely outside the jit).
    """
    return _execute_stack(plan, prepare_stack(plan, layers), frames)


# SRPlan is frozen/hashable -> static; layers/frames are pytree args, so the
# jit cache is keyed on (plan, layer structure & shapes, batch shape).
_execute_jit = jax.jit(_execute, static_argnums=0)


def build_executor(
    plan: SRPlan,
    layers: Sequence[ConvLayer],
    jit: bool = True,
    shared_jit: bool = True,
) -> Callable[[jax.Array], jax.Array]:
    """Bind plan + weights into ``frames (N,H,W,C) -> HR (N,sH,sW,C)``.

    The callable is compiled ONCE per batch size; every backend — including
    ``kernel`` — runs the whole batch inside that single jitted call.

    ``shared_jit=True`` dispatches through the module-level jit (one global
    cache shared with ``run`` — compiled programs are pinned for the
    process).  ``shared_jit=False`` gives the executor its OWN jit wrapper
    that dies with the returned callable, so nothing at this layer pins the
    program once the caller (the session's ``PlanCache``) drops it; any
    residual reuse on a rebuild comes from jax's internal bounded
    compilation caches, not from this module.
    """
    plan.check_invariants()
    bound = tuple(layers)
    if not jit:
        fn = _execute
    elif shared_jit:
        fn = _execute_jit
    else:
        fn = jax.jit(_execute, static_argnums=0)
    return functools.partial(fn, plan, bound)


def build_stack_executor(
    plan: SRPlan,
    stack: PreparedStack,
    *,
    donate_frames: bool = False,
) -> Callable[[jax.Array], jax.Array]:
    """The serving executor: bind plan + a :class:`PreparedStack` into
    ``frames (N,H,W,C) -> HR (N,sH,sW,C)``.

    The stack rides in as a pytree argument on every call (device-resident
    arrays — dispatch cost only), so the compiled program contains no
    weight preparation.  ``donate_frames=True`` compiles with the frame
    batch donated (``donate_argnums``): XLA may reuse the bucket-sized
    slab's memory for same-sized intermediates (e.g. the compute-dtype
    cast of the frames) and releases it at its last use instead of
    pinning it for the whole call — note the HR output itself is
    ``scale^2`` x larger than the input, so for ``scale > 1`` the output
    buffer never aliases the donated slab.  Callers must treat the input
    array as CONSUMED.  The executor gets its own jit
    wrapper (same lifetime rationale as ``build_executor(shared_jit=False)``:
    evicting the cache entry drops the program), exposed as ``.jitted`` on
    the returned callable so tests can assert its trace count.
    """
    plan.check_invariants()
    donate = (2,) if donate_frames else ()
    jitted = jax.jit(_execute_stack, static_argnums=0, donate_argnums=donate)
    fn = functools.partial(jitted, plan, stack)
    fn.jitted = jitted
    fn.donates_frames = donate_frames
    return fn


def _band_features(
    plan: SRPlan, stack: PreparedStack, slabs: jax.Array, bounds: jax.Array
) -> jax.Array:
    """Conv-stack features over an explicit band-slab stack.

    ``slabs`` is (k, rows, W, C0) with rows = R + 2L under ``halo`` (the
    ``core.fusion.halo_slabs`` geometry, ``bounds`` carrying each slab's
    valid-row interval) and rows = R otherwise.  Per band this runs the
    SAME per-slab computation as the full-frame path — the tilted
    backend maps the identical ``tilted_fused_band`` closure, the kernel
    backend runs the identical sequential band grid — so each output
    band is bit-identical to the corresponding band of a full launch.
    The reference backend has no band decomposition and cannot serve
    partial dispatches.
    """
    R, L = plan.band_rows, plan.num_layers
    policy = plan.vertical_policy
    if plan.backend == "kernel":
        from repro.kernels import ops  # local import: kernels are optional

        return ops.tilted_fused_band_stack(
            slabs,
            tile_cols=plan.tile_cols,
            vertical_policy=policy,
            row_bounds=bounds if policy == "halo" else None,
            compute_dtype=slabs.dtype,
            packed=stack.packed,
        )
    if plan.backend != "tilted":
        raise ValueError(
            f"backend {plan.backend!r} cannot serve partial-band dispatches "
            "(no band decomposition); use 'tilted' or 'kernel'"
        )
    layers = stack.layers
    if policy in ("zero", "replicate"):
        return jax.vmap(
            lambda band: tilted_fused_band(
                band, layers, plan.tile_cols, row_pad=policy
            )
        )(slabs)
    out = jax.vmap(
        lambda band, l, h: tilted_fused_band(
            band, layers, plan.tile_cols, row_pad="zero", row_valid=(l, h)
        )
    )(slabs, bounds[:, 0], bounds[:, 1])
    return out[:, L : L + R]  # crop the recompute margin


def _execute_band_stack(
    plan: SRPlan, stack: PreparedStack, slabs: jax.Array, bounds: jax.Array
) -> jax.Array:
    """Partial-band serving program: band slabs -> HR bands.

    The temporal delta path's executor body: (k, rows, W, C) input slabs
    (plus (k, 2) int32 valid-row bounds, meaningful under ``halo`` and
    dead-code-eliminated otherwise) -> (k, R*s, W*s, C) upscaled bands.
    The epilogue is row-block local (see :func:`sr_epilogue`), so running
    it on each band's own LR rows reproduces the full-frame epilogue's
    bytes for those rows exactly.
    """
    if slabs.ndim != 4:
        raise ValueError(
            f"expected a band-slab batch (k, rows, W, C), got {slabs.shape}"
        )
    in_dtype = slabs.dtype
    x = slabs.astype(compute_dtype_for(plan.precision))
    feats = _band_features(plan, stack, x, bounds)
    if plan.vertical_policy == "halo":
        L = plan.num_layers
        lr = x[:, L : L + plan.band_rows]  # each slab's own (anchor) rows
    else:
        lr = x
    return sr_epilogue(plan, lr, feats, in_dtype)


def build_band_executor(
    plan: SRPlan, stack: PreparedStack
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Compile the partial-band executor ``(slabs, bounds) -> HR bands``.

    Same shape as :func:`build_stack_executor` (own jit wrapper exposed
    as ``.jitted``, stack as a pytree argument) but never donates: band
    slabs are a small fraction of a frame and the splice path reads the
    dispatch result immediately.
    """
    plan.check_invariants()
    if plan.backend == "reference":
        raise ValueError(
            "reference backend cannot serve partial-band dispatches"
        )
    jitted = jax.jit(_execute_band_stack, static_argnums=0)
    fn = functools.partial(jitted, plan, stack)
    fn.jitted = jitted
    fn.donates_frames = False
    return fn


def executor_artifacts(
    plan: SRPlan,
    stack: Optional[PreparedStack],
    batch: int,
    dtype=jnp.float32,
    *,
    layers: Optional[Sequence[ConvLayer]] = None,
    compiled: bool = True,
) -> dict:
    """The compiler-facing artifacts of the serving executor for one
    bucket: the traced jaxpr text and (``compiled=True``) the optimized
    HLO text — what ``repro.analysis.program_audit`` scans for forbidden
    patterns (quant ops, host callbacks/transfers, silent upcasts).

    Pass ``stack`` to audit exactly what serving runs
    (``_execute_stack`` over a :class:`PreparedStack`); pass ``layers``
    with ``stack=None`` to build the stack here.  Tracing is abstract
    (``ShapeDtypeStruct`` input) so no frame buffer is allocated; the
    compile (HLO path only) hits jax's internal caches when the session
    already compiled this key.
    """
    if stack is None:
        if layers is None:
            raise ValueError("need a PreparedStack or raw layers")
        stack = prepare_stack(plan, layers)
    spec = jax.ShapeDtypeStruct((int(batch), *plan.lr_shape), dtype)
    jaxpr = jax.make_jaxpr(
        functools.partial(_execute_stack, plan, stack)
    )(spec)
    out = {
        "plan": plan,
        "batch": int(batch),
        "dtype": np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype))).name,
        "jaxpr": str(jaxpr),
        "hlo": None,
    }
    if compiled:
        jitted = jax.jit(_execute_stack, static_argnums=0)
        out["hlo"] = jitted.lower(plan, stack, spec).compile().as_text()
    return out


def output_spec(
    plan: SRPlan, layers: Sequence[ConvLayer], batch: int, dtype
) -> jax.ShapeDtypeStruct:
    """The shape/dtype the executor emits for a ``(batch, *lr_shape)``
    input of ``dtype`` — derived by abstract evaluation, no compile.

    This is the one authority on the executor's output contract; degenerate
    serving paths (empty clips/requests) use it so their zero-length output
    matches a real batch exactly.
    """
    fn = build_executor(plan, layers, jit=False)
    return jax.eval_shape(
        fn, jax.ShapeDtypeStruct((batch, *plan.lr_shape), dtype)
    )


def run(plan: SRPlan, layers: Sequence[ConvLayer], frames: jax.Array) -> jax.Array:
    """One-shot convenience: run a frame batch through the plan's executor.

    Hits jax's jit cache on repeated calls with the same plan and layer
    structure — the serving steady state pays one dispatch, no retrace.
    """
    return _execute_jit(plan, tuple(layers), frames)
