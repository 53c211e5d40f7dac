"""Tilted layer fusion — pure-JAX reference executor (paper §II).

Three executors over the same 3x3-conv stack, cross-validated in tests:

* :func:`conv_stack_reference` — plain full-image, layer-by-layer SAME conv.
  Semantically the ground truth; also the model of the paper's *baseline*
  accelerators ([11]/[12]) that round-trip every feature map through DRAM.
* :func:`tilted_fused_band` — the paper's contribution: a single band swept
  by parallelepipedal column tiles via ``lax.scan``; the scan carry is the
  overlap buffer (the functional analogue of the queue-addressed SRAM of
  §III-F).  Horizontally EXACT w.r.t. the reference — the whole point of the
  tilt is that left/right boundary information is preserved.
* :func:`run_banded` — full-image driver: vertical band partitioning with a
  configurable boundary policy (``zero`` = paper's block-conv rows,
  ``halo`` = exact recompute margins, ``replicate`` = edge padding).

The Pallas TPU kernel in ``repro.kernels.tilted_fusion`` implements the same
schedule with the overlap buffer in persistent VMEM scratch; this module is
its oracle.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiling import TileSchedule, make_schedule, single_tile_cols

__all__ = [
    "ConvLayer",
    "conv_stack_reference",
    "tilted_fused_band",
    "run_banded",
    "halo_slabs",
    "max_channels",
]


@dataclasses.dataclass
class ConvLayer:
    """One fused 3x3 conv layer: HWIO weights, bias, ReLU flag."""

    w: jax.Array  # (3, 3, Ci, Co)
    b: jax.Array  # (Co,)
    relu: bool = True

    @property
    def ci(self) -> int:
        return self.w.shape[2]

    @property
    def co(self) -> int:
        return self.w.shape[3]


jax.tree_util.register_dataclass(
    ConvLayer, data_fields=["w", "b"], meta_fields=["relu"]
)


def max_channels(layers: Sequence[ConvLayer]) -> int:
    """max(Ch_i) over all feature maps F_0..F_L (paper's buffer bound)."""
    return max([layers[0].ci] + [l.co for l in layers])


# ----------------------------------------------------------------------
# Reference layerwise executor
# ----------------------------------------------------------------------
def _conv2d(x: jax.Array, w: jax.Array, padding) -> jax.Array:
    """NHWC/HWIO conv on a single (H, W, C) image."""
    return jax.lax.conv_general_dilated(
        x[None],
        w,
        window_strides=(1, 1),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )[0]


def conv_stack_reference(x: jax.Array, layers: Sequence[ConvLayer]) -> jax.Array:
    """Full-image layer-by-layer execution with SAME zero padding.

    This is both the numerical oracle for the fused executors and the model
    of layer-by-layer accelerators: each intermediate here corresponds to a
    full feature-map DRAM round trip (bandwidth modelled in
    ``core.analysis.dram_traffic``).
    """
    f = x
    for layer in layers:
        f = _conv2d(f, layer.w, "SAME") + layer.b
        if layer.relu:
            f = jax.nn.relu(f)
    return f


# ----------------------------------------------------------------------
# Tilted fused executor (one band)
# ----------------------------------------------------------------------
def _conv_tile(f: jax.Array, layer: ConvLayer, row_pad: str) -> jax.Array:
    """3x3 conv of a (R, C+2, Ci) tile slab -> (R, C, Co).

    Columns are VALID (the slab already carries the +-1 column halo, courtesy
    of the overlap buffer); rows are padded per the band policy.
    """
    if row_pad == "zero":
        f = jnp.pad(f, ((1, 1), (0, 0), (0, 0)))
    elif row_pad == "replicate":
        f = jnp.pad(f, ((1, 1), (0, 0), (0, 0)), mode="edge")
    else:  # pragma: no cover - guarded by caller
        raise ValueError(f"unknown row_pad {row_pad!r}")
    out = _conv2d(f, layer.w, "VALID") + layer.b
    if layer.relu:
        out = jax.nn.relu(out)
    return out


def tilted_fused_band(
    x: jax.Array,
    layers: Sequence[ConvLayer],
    tile_cols: Optional[int] = None,
    row_pad: str = "zero",
    row_valid: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Run the tilted layer-fusion sweep over one band.

    Args:
      x: band input, shape ``(R, W, Ch0)``.
      layers: the fused conv stack (L layers).
      tile_cols: C, the parallelepiped width; ``None`` sweeps the band in
        one tile (:func:`~repro.core.tiling.single_tile_cols`), the
        engine's default off the ``kernel`` backend.  The ASIC's is 8.
      row_pad: vertical boundary policy *within* the band.
      row_valid: optional ``(lo, hi)`` band-row range that is real image
        content; rows outside it are phantom (e.g. the zero margin a
        ``halo`` band carries past the image edge) and are re-zeroed after
        every layer so they behave exactly like SAME padding.

    Returns:
      ``(R, W, Ch_L)`` — bit-compatible with
      ``conv_stack_reference`` horizontally (rows differ only per band
      policy, which is the caller's concern — see :func:`run_banded`).

    Implementation notes (mirrors the hardware):
      * the scan carry is the overlap buffer, shape ``(L, R, 2, Chmax)`` —
        feature index 0 is the *input* stream (so only C fresh input columns
        stream per tile, the source of the DRAM-bandwidth reduction);
      * phantom columns (absolute col < 0 or >= W) are zeroed after every
        layer so that edge effects match SAME padding exactly
        (``tiling.phantom_mask``).
    """
    R, W, C0 = x.shape
    L = len(layers)
    if tile_cols is None:
        tile_cols = single_tile_cols(W, L)
    if tile_cols < 2:
        raise ValueError("tile_cols must be >= 2 (overlap hand-off is 2 columns)")
    sched = make_schedule(width=W, tile_cols=tile_cols, num_layers=L)
    K, C = sched.num_tiles, tile_cols
    chmax = max_channels(layers)
    dtype = x.dtype

    # Fresh input stream: tile k consumes absolute input columns
    # [k*C + 1, k*C + C]; pad the image with zeros out to column K*C.
    x_pad = jnp.pad(x, ((0, 0), (0, K * C + 1 - W), (0, 0)))
    xs = x_pad[:, 1 : K * C + 1, :]  # columns 1 .. K*C
    xs = xs.reshape(R, K, C, C0).transpose(1, 0, 2, 3)  # (K, R, C, C0)

    # Overlap buffer init: all zeros except feature 0 holds input columns
    # [-1, 0] = [zero-pad, first real column].
    overlap0 = jnp.zeros((L, R, 2, chmax), dtype)
    overlap0 = overlap0.at[0, :, 1, :C0].set(x[:, 0, :])

    col_idx = jnp.arange(C)
    if row_valid is not None:
        row_mask = (jnp.arange(R) >= row_valid[0]) & (jnp.arange(R) < row_valid[1])
    else:
        row_mask = None

    def tile_step(overlap, inputs):
        k, fresh = inputs
        new_overlap = overlap
        # Assemble the input slab: 2 overlap columns ++ C fresh columns.
        f = jnp.concatenate([overlap[0, :, :, :C0], fresh], axis=1)  # (R, C+2, C0)
        new_overlap = new_overlap.at[0, :, :, :C0].set(f[:, -2:, :])
        out = None
        for l, layer in enumerate(layers):
            g = _conv_tile(f, layer, row_pad)  # (R, C, Co)
            # Zero phantom columns: output cols are k*C - l + [0, C).
            abs_cols = k * C - l + col_idx
            valid = (abs_cols >= 0) & (abs_cols < W)
            g = jnp.where(valid[None, :, None], g, 0)
            if row_mask is not None:
                g = jnp.where(row_mask[:, None, None], g, 0)
            if l < L - 1:
                left = overlap[l + 1, :, :, : layer.co]  # F_{l+1} left 2 cols
                new_overlap = new_overlap.at[l + 1, :, :, : layer.co].set(
                    g[:, -2:, :]
                )
                f = jnp.concatenate([left, g], axis=1)  # (R, C+2, Co)
            else:
                out = g
        return new_overlap, out

    ks = jnp.arange(K)
    _, tiles = jax.lax.scan(tile_step, overlap0, (ks, xs))
    # tiles: (K, R, C, ChL). Tile k's output occupies absolute columns
    # [k*C - (L-1), k*C - (L-1) + C) -> contiguous; slice off the tilt.
    out = tiles.transpose(1, 0, 2, 3).reshape(R, K * C, layers[-1].co)
    return jax.lax.slice_in_dim(out, L - 1, L - 1 + W, axis=1)


# ----------------------------------------------------------------------
# Halo slab marshalling (shared by the tilted and Pallas backends)
# ----------------------------------------------------------------------
def halo_slabs(frames: jax.Array, band_rows: int, num_layers: int):
    """Marshal halo slabs: (N, H, W, C0) -> (N*B, R+2L, W, C0) + (N*B, 2).

    Each band's slab is the (R + 2L)-row window of the zero-padded frame
    starting at its own row offset; the int32 bounds mark which slab rows
    are real image content (``[lo, hi)`` in slab coordinates).  Rows
    outside the bounds are phantom and must be re-zeroed after every conv
    layer (``tilted_fused_band``'s ``row_valid`` / the kernel's
    ``row_bounds``) so they behave exactly like SAME padding; cropping L
    rows per side afterwards reproduces the full-image result.

    This is the ONE definition of the engine's halo geometry — both the
    pure-JAX executor and the Pallas kernel marshalling consume it.
    """
    N, H, W, C0 = frames.shape
    R, L = band_rows, num_layers
    B = H // R
    slab = R + 2 * L
    padded = jnp.pad(frames, ((0, 0), (L, L), (0, 0), (0, 0)))
    slabs = jnp.stack(
        [padded[:, b * R : b * R + slab] for b in range(B)], axis=1
    )  # (N, B, R+2L, W, C0)
    starts = np.arange(B) * R
    lo = np.clip(L - starts, 0, slab)
    hi = np.clip(L + H - starts, 0, slab)
    bounds = np.tile(np.stack([lo, hi], axis=1), (N, 1)).astype(np.int32)
    return slabs.reshape(N * B, slab, W, C0), jnp.asarray(bounds)


# ----------------------------------------------------------------------
# Full-image banded driver
# ----------------------------------------------------------------------
def run_banded(
    image: jax.Array,
    layers: Sequence[ConvLayer],
    band_rows: int = 60,
    tile_cols: Optional[int] = None,
    vertical_policy: str = "zero",
) -> jax.Array:
    """Tilted layer fusion over a full image, band by band.

    vertical_policy:
      * ``"zero"`` — the paper's scheme: each R-row band is convolved with
        zero padding at its top/bottom edges (block convolution vertically).
        Information at the 5 interior band boundaries of a 360-row image is
        discarded; the PSNR penalty is <0.2 dB (reproduced in
        ``benchmarks/psnr_penalty.py``).
      * ``"halo"`` — exact: each band is extracted with an L-row margin on
        each side and the margin is cropped after the fused stack, trading
        ~2*L/R recompute for bit-exactness with the full-image reference.
      * ``"replicate"`` — zero-cost variant of "zero" with edge-replicate
        padding (usually a slightly smaller PSNR penalty on natural images).
    """
    H, W, _ = image.shape
    L = len(layers)
    if H % band_rows != 0:
        raise ValueError(f"image height {H} must be a multiple of band_rows {band_rows}")
    n_bands = H // band_rows
    outs = []
    for b in range(n_bands):
        r0 = b * band_rows
        if vertical_policy in ("zero", "replicate"):
            band = image[r0 : r0 + band_rows]
            out = tilted_fused_band(band, layers, tile_cols, row_pad=vertical_policy)
        elif vertical_policy == "halo":
            lo = max(0, r0 - L)
            hi = min(H, r0 + band_rows + L)
            band = image[lo:hi]
            # zero-pad to a full halo if clipped by the image edge; the pad
            # rows are phantom and must stay zero through every layer
            # (row_valid) to match SAME padding semantics exactly.
            pad_top = L - (r0 - lo)
            pad_bot = L - (hi - r0 - band_rows)
            band = jnp.pad(band, ((pad_top, pad_bot), (0, 0), (0, 0)))
            out = tilted_fused_band(
                band,
                layers,
                tile_cols,
                row_pad="zero",
                row_valid=(pad_top, pad_top + hi - lo),
            )
            out = out[L : L + band_rows]
        else:
            raise ValueError(f"unknown vertical_policy {vertical_policy!r}")
        outs.append(out)
    return jnp.concatenate(outs, axis=0)
