"""Public jit'd wrappers around the Pallas kernels.

These handle the host-side data marshalling that the accelerator's DMA
engine performs in the paper: channel padding to TPU-friendly widths,
building the fresh-column stream, and undoing the output tilt.

``interpret`` defaults to True on the CPU platform (kernel body executed
for validation) and False on TPU (compiled to Mosaic); any other platform
is an error — the kernels are Mosaic TPU kernels.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.fusion import ConvLayer, halo_slabs
from repro.core.tiling import make_schedule
from repro.kernels import conv3x3 as _conv3x3
from repro.kernels import tilted_fusion as _tilted

__all__ = [
    "conv3x3",
    "tilted_fused_stack",
    "tilted_fused_frames",
    "tilted_fused_band_stack",
    "pack_layers",
    "pack_stack",
    "PackedLayers",
    "default_interpret",
]

VERTICAL_POLICIES = ("zero", "halo", "replicate")


def default_interpret() -> bool:
    """Interpret on the CPU platform only; a TPU always compiles."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels target TPU (or CPU interpret mode); "
            f"the default platform is {platform!r}"
        )
    return platform == "cpu"


def _round_up(x: int, m: int) -> int:
    # Delegates to the kernel's canonical padding rule so ops-level packing
    # and the static analyser (repro.analysis.plan_check) count identically.
    return _tilted.round_up_channels(x, m)


def pack_layers(layers: Sequence[ConvLayer], chp: Optional[int] = None, dtype=None):
    """Zero-pad a heterogeneous conv stack to uniform (L,3,3,Chp,Chp) + (L,Chp).

    Padded input/output channels carry zero weights and biases, so they stay
    identically zero through every ReLU layer — the kernel never masks
    channels. ``chp`` defaults to max(Ch) rounded up to 8 (sublane); pass 128
    for full MXU lane alignment (§Perf studies both).
    """
    chmax = max([layers[0].ci] + [l.co for l in layers])
    chp = chp or _round_up(chmax, 8)
    if chp < chmax:
        raise ValueError(f"chp={chp} < max channels {chmax}")
    dtype = dtype or layers[0].w.dtype
    L = len(layers)
    w = jnp.zeros((L, 3, 3, chp, chp), dtype)
    b = jnp.zeros((L, chp), dtype)
    for i, l in enumerate(layers):
        w = w.at[i, :, :, : l.ci, : l.co].set(l.w.astype(dtype))
        b = b.at[i, : l.co].set(l.b.astype(dtype))
    return w, b, chp


@dataclasses.dataclass
class PackedLayers:
    """A conv stack in the kernel's packed storage form, plus its static
    facts (channel pad, ReLU flags, real output channels).

    Packing happens where this object is built — typically ONCE per weight
    stack, outside any jitted serving call (``engine.executor.prepare_stack``)
    — so the per-batch kernel launch takes the padded ``(L,3,3,Chp,Chp)`` /
    ``(L,Chp)`` arrays as plain device-resident inputs instead of re-running
    the zero-pad scatter on every forward.
    """

    w: jax.Array  # (L, 3, 3, Chp, Chp)
    b: jax.Array  # (L, Chp)
    chp: int
    relu: Tuple[bool, ...]
    out_channels: int  # Ch_L of the real (unpadded) stack

    @property
    def num_layers(self) -> int:
        return len(self.relu)


jax.tree_util.register_dataclass(
    PackedLayers,
    data_fields=["w", "b"],
    meta_fields=["chp", "relu", "out_channels"],
)


def pack_stack(
    layers: Sequence[ConvLayer], chp: Optional[int] = None, dtype=None
) -> PackedLayers:
    """Pack a conv stack for the kernel (``pack_layers``) and bundle the
    static facts the launch needs, so callers can pre-pack device-resident
    weights and pass them via ``tilted_fused_frames(..., packed=...)``."""
    w, b, chp = pack_layers(layers, chp, dtype=dtype)
    return PackedLayers(
        w=w,
        b=b,
        chp=chp,
        relu=tuple(bool(l.relu) for l in layers),
        out_channels=layers[-1].co,
    )


def _tilted_fused_bands(
    xb: jax.Array,  # (B, R, W, C0) band-major input
    packed: PackedLayers,
    *,
    tile_cols: int,
    add_anchor: bool,
    anchor_repeats: int,
    interpret: bool,
    row_policy: str = "zero",
    row_bounds: Optional[jax.Array] = None,
    compute_dtype=None,
) -> jax.Array:
    """Run the Pallas kernel over a flat batch of bands -> (B, R, W, ChL).

    The band axis is the kernel's sequential grid axis: scratch (overlap
    queue + residual ring) is re-zeroed whenever the column index wraps, so
    bands from different frames can share one launch — this is what lets the
    engine serve a whole frame batch with a single ``pallas_call``.
    """
    B, R, W, C0 = xb.shape
    C, L = tile_cols, packed.num_layers
    sched = make_schedule(width=W, tile_cols=C, num_layers=L)
    K = sched.num_tiles
    chp, co_l = packed.chp, packed.out_channels

    c0p = _round_up(C0, 8)

    xb = jnp.pad(xb, ((0, 0), (0, 0), (0, 0), (0, c0p - C0)))
    # Fresh stream: tile k consumes input columns [k*C + 1, k*C + C].
    xs = jnp.pad(xb, ((0, 0), (0, 0), (0, K * C + 1 - W), (0, 0)))[:, :, 1 : K * C + 1, :]
    first_col = xb[:, :, 0:1, :]

    out = _tilted.tilted_fusion_call(
        xs,
        first_col,
        packed.w,
        packed.b,
        width=W,
        tile_cols=C,
        relu_flags=list(packed.relu),
        add_anchor=add_anchor,
        in_channels=C0,
        anchor_repeats=anchor_repeats,
        row_policy=row_policy,
        row_bounds=row_bounds,
        compute_dtype=compute_dtype,
        interpret=interpret,
    )
    # Undo the tilt: tile k's block holds F_L columns [k*C - (L-1), ...+C).
    out = out.reshape(B, R, K * C, chp)
    out = jax.lax.slice(out, (0, 0, L - 1, 0), (B, R, L - 1 + W, co_l))
    return out


def tilted_fused_stack(
    x: jax.Array,
    layers: Sequence[ConvLayer],
    *,
    band_rows: int = 60,
    tile_cols: int = 8,
    chp: Optional[int] = None,
    add_anchor: bool = False,
    anchor_repeats: int = 9,
    vertical_policy: str = "zero",
    compute_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tilted layer fusion of a full (H, W, C0) image via the Pallas kernel.

    Returns (H, W, Ch_L) features (or anchored output when ``add_anchor``),
    numerically identical to ``ref.tilted_fused_stack_ref``.
    """
    H, W, C0 = x.shape
    out = tilted_fused_frames(
        x[None],
        layers,
        band_rows=band_rows,
        tile_cols=tile_cols,
        chp=chp,
        add_anchor=add_anchor,
        anchor_repeats=anchor_repeats,
        vertical_policy=vertical_policy,
        compute_dtype=compute_dtype,
        interpret=interpret,
    )
    return out.reshape(H, W, out.shape[-1])


def tilted_fused_frames(
    frames: jax.Array,
    layers: Optional[Sequence[ConvLayer]] = None,
    *,
    band_rows: int = 60,
    tile_cols: int = 8,
    chp: Optional[int] = None,
    add_anchor: bool = False,
    anchor_repeats: int = 9,
    vertical_policy: str = "zero",
    compute_dtype=None,
    interpret: Optional[bool] = None,
    packed: Optional[PackedLayers] = None,
) -> jax.Array:
    """Tilted layer fusion of a batch of frames (N, H, W, C0) -> (N, H, W, ChL).

    All N * (H / band_rows) bands are folded into the kernel's sequential
    band grid axis, so the whole batch is ONE ``pallas_call`` launch.

    ``vertical_policy`` selects the band boundary treatment (``zero`` |
    ``halo`` | ``replicate``, same semantics as ``core.fusion.run_banded``):
    ``zero``/``replicate`` run the R-row bands directly with the matching
    in-kernel row padding; ``halo`` marshals (R + 2L)-row slabs with
    per-band valid-row bounds and crops the recompute margin, so the result
    is exact w.r.t. the full-image reference up to matmul accumulation
    order.  ``compute_dtype`` is the kernel's on-chip feature-map dtype
    (defaults to the input dtype; MXU accumulation stays fp32).

    ``packed`` supplies a pre-packed weight stack (:func:`pack_stack`); when
    given, ``layers`` is ignored and the per-call weight pad/scatter is
    skipped — the serving engine packs once per weight stack and reuses the
    device-resident arrays across every batch.
    """
    N, H, W, C0 = frames.shape
    R = band_rows
    if H % R != 0:
        raise ValueError(f"height {H} must be a multiple of band_rows {R}")
    if vertical_policy not in VERTICAL_POLICIES:
        raise ValueError(
            f"vertical_policy {vertical_policy!r} not in {VERTICAL_POLICIES}"
        )
    if packed is None:
        if layers is None:
            raise ValueError("pass either layers or packed")
        packed = pack_stack(layers, chp, dtype=compute_dtype)
    interpret = default_interpret() if interpret is None else interpret
    L = packed.num_layers
    if vertical_policy == "halo":
        slabs, bounds = halo_slabs(frames, R, L)
        out = _tilted_fused_bands(
            slabs,
            packed,
            tile_cols=tile_cols,
            add_anchor=add_anchor,
            anchor_repeats=anchor_repeats,
            interpret=interpret,
            row_policy="zero",
            row_bounds=bounds,
            compute_dtype=compute_dtype,
        )
        out = out[:, L : L + R]  # crop the recompute margin
    else:
        out = _tilted_fused_bands(
            frames.reshape(N * (H // R), R, W, C0),
            packed,
            tile_cols=tile_cols,
            add_anchor=add_anchor,
            anchor_repeats=anchor_repeats,
            interpret=interpret,
            row_policy=vertical_policy,
            compute_dtype=compute_dtype,
        )
    return out.reshape(N, H, W, out.shape[-1])


def tilted_fused_band_stack(
    bands: jax.Array,
    layers: Optional[Sequence[ConvLayer]] = None,
    *,
    tile_cols: int = 8,
    vertical_policy: str = "zero",
    row_bounds: Optional[jax.Array] = None,
    chp: Optional[int] = None,
    compute_dtype=None,
    interpret: Optional[bool] = None,
    packed: Optional[PackedLayers] = None,
) -> jax.Array:
    """Tilted fusion over an explicit band stack (k, rows, W, C0) -> (k, R, W, ChL).

    The partial-band entry point for temporal delta serving: the caller
    has already marshalled per-band input slabs (an arbitrary subset of
    one or more frames' bands) and, under ``halo``, the matching
    per-slab valid-row bounds in the ``core.fusion.halo_slabs``
    geometry.  ``tilted_fused_frames`` cannot serve this case — its
    internal ``halo_slabs`` would borrow margin rows from whatever band
    happens to be adjacent in the stack, which for a subset is not the
    spatial neighbor.

    Under ``halo`` the slabs carry ``rows = R + 2L`` and the recompute
    margin is cropped from the output; under ``zero``/``replicate`` the
    slabs are the bare R rows.  The bands run on the kernel's sequential
    band grid axis with scratch re-zeroed per band, so each output band
    is byte-identical to the same band of a full-frame launch — the
    invariant the delta path's bit-exact splice rests on.
    """
    if bands.ndim != 4:
        raise ValueError(f"bands must be (k, rows, W, C0), got {bands.shape}")
    if vertical_policy not in VERTICAL_POLICIES:
        raise ValueError(
            f"vertical_policy {vertical_policy!r} not in {VERTICAL_POLICIES}"
        )
    if packed is None:
        if layers is None:
            raise ValueError("pass either layers or packed")
        packed = pack_stack(layers, chp, dtype=compute_dtype)
    interpret = default_interpret() if interpret is None else interpret
    if vertical_policy == "halo":
        L = packed.num_layers
        R = bands.shape[1] - 2 * L
        if R <= 0:
            raise ValueError(
                f"halo slabs need rows > 2L; got rows={bands.shape[1]}, L={L}"
            )
        if row_bounds is None:
            raise ValueError("halo band stacks require row_bounds")
        out = _tilted_fused_bands(
            bands,
            packed,
            tile_cols=tile_cols,
            add_anchor=False,
            anchor_repeats=9,
            interpret=interpret,
            row_policy="zero",
            row_bounds=row_bounds,
            compute_dtype=compute_dtype,
        )
        return out[:, L : L + R]  # crop the recompute margin
    return _tilted_fused_bands(
        bands,
        packed,
        tile_cols=tile_cols,
        add_anchor=False,
        anchor_repeats=9,
        interpret=interpret,
        row_policy=vertical_policy,
        compute_dtype=compute_dtype,
    )


def conv3x3(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    tile_cols: int = 8,
    relu: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-layer vectorwise 3x3 conv (the layerwise-baseline datapath)."""
    interpret = default_interpret() if interpret is None else interpret
    return _conv3x3.conv3x3_call(
        x, w, b, tile_cols=tile_cols, relu=relu, interpret=interpret
    )
