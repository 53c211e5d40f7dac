"""Pallas TPU kernel: tilted layer fusion (the paper's chip, one core/band).

TPU-native adaptation of the accelerator (DESIGN.md §2):

* HBM -> VMEM streaming replaces DRAM -> SRAM: because of the tilt, each
  grid step consumes a *disjoint* C-column input slab — overlapping halo
  reads are converted into clean non-overlapping ``BlockSpec`` streaming
  (this is exactly the paper's bandwidth insight, expressed as a BlockSpec).
* The overlap SRAM queue (paper §III-F) becomes a persistent VMEM scratch
  array ``(L, R, 2, Chp)``: TPU grids execute sequentially, so scratch
  carries the last two columns of every fused feature map from tile k to
  tile k+1.  It is re-zeroed when the column index wraps (new band).
* The residual SRAM (paper eq. 3) becomes a ``(R, C+L, Ch0)`` VMEM ring that
  retains exactly the last C+L input columns — the anchor for tile k's
  output is always the ring's leading C columns.
* The 28x3x(5x3)-MAC diagonal PE array becomes 9 shifted MXU matmuls per
  layer: ``(R*C, Chp) @ (Chp, Chp)`` — the diagonal partial-sum accumulation
  of the vectorwise dataflow is what a systolic matmul performs internally.

Channel counts are padded to a uniform ``Chp`` (multiple of 8, up to 128 for
full MXU lanes); padded weights/biases are zero, so padded channels stay
identically zero through ReLU — no masking needed on channels.  Phantom
*columns* (outside the image) ARE masked every layer, which keeps the kernel
bit-compatible with SAME-padded convolution (see ``core.tiling``).

The kernel covers the full ``SRPlan`` space:

* ``row_policy`` selects the vertical boundary treatment of each band —
  ``zero`` (the paper's block-conv rows) or ``replicate`` (edge-row padding
  at every layer, matching ``core.fusion._conv_tile``).
* ``row_bounds`` (per-band ``[lo, hi)`` SMEM scalars) marks real-image rows
  of a halo slab; rows outside are phantom and re-zeroed after every layer,
  so an (R + 2L)-row slab cropped by L rows per side reproduces the exact
  full-image result (the engine's ``halo`` policy).
* ``compute_dtype`` is the on-chip feature-map dtype: bf16 plans hold the
  overlap queue / residual ring in bf16 and round every fused feature map to
  bf16, while MXU accumulation stays fp32 — the TPU-native reading of the
  chip's reduced-precision datapath.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tilted_fusion_kernel",
    "tilted_fusion_call",
    "round_up_channels",
    "scratch_shapes",
    "kernel_buffers",
]


def round_up_channels(n: int, multiple: int = 8) -> int:
    """The kernel's channel-padding rule: round up to the TPU sublane
    multiple (8).  ``ops.pack_layers`` and the static analyser both go
    through this, so padded storage and the verifier's byte accounting can
    never drift apart."""
    return -(-int(n) // multiple) * multiple


def scratch_shapes(num_layers: int, band_rows: int, tile_cols: int,
                   chp: int, c0p: int):
    """The kernel's persistent VMEM scratch shapes — ``(overlap_queue,
    residual_ring)`` — as plain tuples.

    This is the ONE definition of the scratch geometry: the
    ``pallas_call`` launch below allocates exactly these shapes, and the
    static plan verifier (``repro.analysis.plan_check``) computes its
    on-chip budget from them.
    """
    overlap = (num_layers, band_rows, 2, chp)
    residual = (band_rows, tile_cols + num_layers, c0p)
    return overlap, residual


def _elems(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def kernel_buffers(
    *,
    channels,  # Sequence[int]: feature-map channel counts F_0..F_L
    band_rows: int,
    tile_cols: int,
    chp: int = None,
) -> dict:
    """Static introspection of every on-chip buffer ``tilted_fusion_call``
    allocates for one grid step, in ELEMENTS (dtype-free).

    For each buffer the entry carries the padded ``shape`` the launch
    really allocates (channels rounded up to the sublane multiple — the
    ``elements`` count) and the ``logical_elements`` the algorithm
    fundamentally retains (unpadded channels — the quantity the paper's
    eqs. (1)-(3) count).  ``repro.analysis.plan_check`` cross-checks the
    logical counts against ``core.analysis.buffer_sizes`` (Table II) and
    budget-gates the padded totals.

    Buffers:
      * ``overlap``    — the persistent overlap-queue VMEM scratch
        (paper eq. 2; here L slots, one per fused layer, vs the RTL's L+2).
      * ``residual``   — the residual-ring VMEM scratch (paper eq. 3).
      * ``stream_in``  — the fresh-column input block + first-column block
        streamed per grid step (the tilt's replacement for half the
        ping-pong pair).
      * ``stream_out`` — the output block written per grid step.
      * ``weights``/``bias`` — the packed weight/bias blocks resident in
        VMEM across the whole launch.
      * ``row_bounds`` — the per-band SMEM scalars (bytes, not elements —
        always int32).
    """
    channels = [int(c) for c in channels]
    L = len(channels) - 1
    if L < 1:
        raise ValueError(f"channels {channels!r} must list F_0..F_L, L >= 1")
    R, C = int(band_rows), int(tile_cols)
    chmax, ch0, chl = max(channels), channels[0], channels[-1]
    chp = int(chp) if chp else round_up_channels(chmax)
    c0p = round_up_channels(ch0)
    overlap_shape, residual_shape = scratch_shapes(L, R, C, chp, c0p)
    buffers = {
        "overlap": {
            "shape": overlap_shape,
            "elements": _elems(overlap_shape),
            "logical_elements": L * R * 2 * chmax,
        },
        "residual": {
            "shape": residual_shape,
            "elements": _elems(residual_shape),
            "logical_elements": ch0 * R * (C + L),
        },
        "stream_in": {
            # x block (1, R, C, c0p) + first_col block (1, R, 1, c0p)
            "shape": (1, R, C + 1, c0p),
            "elements": R * (C + 1) * c0p,
            "logical_elements": ch0 * R * (C + 1),
        },
        "stream_out": {
            "shape": (1, R, C, chp),
            "elements": R * C * chp,
            "logical_elements": chl * R * C,
        },
        "weights": {
            "shape": (L, 3, 3, chp, chp),
            "elements": L * 9 * chp * chp,
            "logical_elements": sum(
                9 * channels[i] * channels[i + 1] for i in range(L)
            ),
        },
        "bias": {
            "shape": (L, chp),
            "elements": L * chp,
            "logical_elements": sum(channels[1:]),
        },
    }
    report = {
        "num_layers": L,
        "band_rows": R,
        "tile_cols": C,
        "chp": chp,
        "c0p": c0p,
        "buffers": buffers,
        # the two int32 bounds a grid step reads; the launch keeps all
        # bands' bounds, (2B,) int32, in SMEM
        "row_bounds_smem_bytes": 2 * 4,
        "scratch_elements": (
            buffers["overlap"]["elements"] + buffers["residual"]["elements"]
        ),
        "total_elements": sum(b["elements"] for b in buffers.values()),
        "total_logical_elements": sum(
            b["logical_elements"] for b in buffers.values()
        ),
    }
    return report


def _conv_tile_mxu(f, w_l, b_l, R: int, C: int, chp: int, acc_dtype, row_policy: str):
    """3x3 conv of one (R, C+2, Chp) slab -> (R, C, Chp) via 9 MXU matmuls.

    ``row_policy`` is the band's vertical boundary treatment: ``zero`` pads
    the +-1 row halo with zeros (the paper's block-conv rows), ``replicate``
    with copies of the band's edge rows — matching ``core.fusion._conv_tile``
    so the kernel stays layer-for-layer compatible with the pure-JAX sweep.
    """
    if row_policy == "replicate":
        frow = jnp.concatenate([f[:1], f, f[-1:]], axis=0)
    else:  # "zero"
        frow = jnp.pad(f, ((1, 1), (0, 0), (0, 0)))
    acc = jnp.zeros((R * C, chp), acc_dtype)
    for dy in range(3):
        for dx in range(3):
            patch = frow[dy:dy + R, dx:dx + C, :]
            acc = acc + jax.lax.dot(
                patch.reshape(R * C, chp),
                w_l[dy, dx],
                preferred_element_type=acc_dtype,
            )
    return acc.reshape(R, C, chp) + b_l[None, None, :]


def tilted_fusion_kernel(
    # inputs (VMEM blocks; row bounds live in SMEM)
    first_col_ref,  # (1, R, 1, C0p)   first real input column of the band
    x_ref,  # (1, R, C, C0p)   fresh input stream slab for tile k
    w_ref,  # (L, 3, 3, Chp, Chp)
    b_ref,  # (L, Chp)
    rows_ref,  # (2B,) int32 SMEM  [valid_lo, valid_hi) of every band, flat
    # outputs
    o_ref,  # (1, R, C, Chp)
    # scratch (persistent across sequential grid steps)
    overlap_ref,  # (L, R, 2, Chp)
    resid_ref,  # (R, C+L, C0p)
    *,
    num_layers: int,
    width: int,
    tile_cols: int,
    band_rows: int,
    chp: int,
    c0p: int,
    relu_flags: Sequence[bool],
    add_anchor: bool,
    in_channels: int,
    anchor_repeats: int,
    row_policy: str = "zero",
    mask_rows: bool = False,
    compute_dtype=jnp.float32,
    acc_dtype=jnp.float32,
):
    L, C, R, W = num_layers, tile_cols, band_rows, width
    k = pl.program_id(1)  # column-tile index (fastest-varying)
    out_dtype = o_ref.dtype
    cdt = compute_dtype

    # ---- new band: reset the overlap queue and the residual ring ----
    @pl.when(k == 0)
    def _init():
        overlap_ref[...] = jnp.zeros_like(overlap_ref)
        resid_ref[...] = jnp.zeros_like(resid_ref)
        # overlap slot for F_0 holds input columns [-1, 0]:
        # col -1 is zero padding; col 0 is the band's first real column.
        # (Slices, not integer indices: Mosaic cannot lower the shape cast
        # an integer-indexed bf16 store needs.)
        first = first_col_ref[0]  # (R, 1, C0p)
        slot = jnp.concatenate([jnp.zeros_like(first), first], axis=1)
        overlap_ref[0, :, :, :c0p] = slot.astype(overlap_ref.dtype)
        # residual ring: after this tile's shift-append the ring spans input
        # columns [-L+1, C]; pre-place col 0 so it lands at ring index L-1.
        resid_ref[:, C + L - 1:, :] = first.astype(resid_ref.dtype)

    fresh = x_ref[0].astype(cdt)  # (R, C, C0p)

    # ---- residual ring: shift left by C, append the fresh slab ----
    if add_anchor:
        ring = resid_ref[...]
        ring = jnp.concatenate([ring[:, C:, :], fresh.astype(resid_ref.dtype)], axis=1)
        resid_ref[...] = ring

    # ---- input slab: 2 overlap columns ++ C fresh columns, pad channels ----
    left0 = overlap_ref[0, :, :, :c0p].astype(cdt)  # (R, 2, C0p)
    f = jnp.concatenate([left0, fresh], axis=1)  # (R, C+2, C0p)
    overlap_ref[0, :, :, :c0p] = f[:, C:, :].astype(overlap_ref.dtype)
    f = jnp.pad(f, ((0, 0), (0, 0), (0, chp - c0p)))

    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, C, 1), 1)
    if mask_rows:
        # Phantom rows (outside this band's valid range, e.g. the zero
        # margin a halo slab carries past the image edge) are re-zeroed
        # after every layer so they behave exactly like SAME padding.
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (R, 1, 1), 0)
        bnd = pl.program_id(0)
        row_ok = (row_iota >= rows_ref[2 * bnd]) & (row_iota < rows_ref[2 * bnd + 1])

    for l in range(L):
        g = _conv_tile_mxu(
            f, w_ref[l].astype(cdt), b_ref[l].astype(acc_dtype),
            R, C, chp, acc_dtype, row_policy,
        )
        if relu_flags[l]:
            g = jnp.maximum(g, 0.0)
        # zero phantom columns: this layer's output covers cols k*C - l + [0, C)
        abs_cols = k * C - l + col_iota
        g = jnp.where((abs_cols >= 0) & (abs_cols < W), g, 0.0)
        if mask_rows:
            g = jnp.where(row_ok, g, 0.0)
        # bf16 plans round every fused feature map to the compute dtype —
        # the on-chip SRAM width — exactly like the pure-JAX sweep does.
        g = g.astype(cdt)
        if l < L - 1:
            left = overlap_ref[l + 1, :, :, :].astype(cdt)  # (R, 2, Chp)
            f = jnp.concatenate([left, g], axis=1)  # (R, C+2, Chp)
            # Store f[:, C:], not g[:, -2:]: the same two columns, but
            # starting at sublane C, a tile boundary.  Storing g's unaligned
            # tail aborts the TPU compiler (lower_to_llo "d >> 32 == 0").
            overlap_ref[l + 1, :, :, :] = f[:, C:, :].astype(overlap_ref.dtype)
        else:
            if add_anchor:
                # anchor = input cols [kC-L+1, kC-L+C) = the ring's head,
                # each channel repeated scale^2 times (channel-major),
                # zero in the lanes past in_channels * repeats.  Built by
                # lane broadcasts and selects in fp32: Mosaic lowers
                # neither a lane ``jnp.repeat`` nor bf16 masks.
                head = resid_ref[:, :C, :].astype(acc_dtype)
                lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chp), 2)
                anchor = sum(
                    jnp.where(lane // anchor_repeats == i, head[:, :, i:i + 1], 0.0)
                    for i in range(in_channels)
                )
                # phantom anchor columns must be masked like g's
                anchor = jnp.where((abs_cols >= 0) & (abs_cols < W), anchor, 0.0)
                g = (g.astype(acc_dtype) + anchor).astype(cdt)
            o_ref[0] = g.astype(out_dtype)


def tilted_fusion_call(
    x_stream: jax.Array,  # (B, R, K*C, C0p) fresh streams per band
    first_col: jax.Array,  # (B, R, 1, C0p)
    w: jax.Array,  # (L, 3, 3, Chp, Chp) zero-padded weights
    b: jax.Array,  # (L, Chp)
    *,
    width: int,
    tile_cols: int,
    relu_flags: Sequence[bool],
    add_anchor: bool,
    in_channels: int,
    anchor_repeats: int = 9,
    row_policy: str = "zero",
    row_bounds: jax.Array = None,  # (B, 2) int32 [valid_lo, valid_hi) per band
    compute_dtype=None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Launch the fused kernel over grid (bands, column tiles).

    ``row_policy`` selects the vertical boundary treatment inside every
    band (``zero`` | ``replicate``); ``row_bounds`` optionally marks each
    band's real-image row range — rows outside it are phantom and re-zeroed
    per layer (the halo-slab mechanism); ``compute_dtype`` is the on-chip
    feature-map dtype (MXU accumulation stays fp32).
    """
    B, R, KC, c0p = x_stream.shape
    L, _, _, chp, _ = w.shape
    C = tile_cols
    K = KC // C
    if add_anchor and in_channels * anchor_repeats > chp:
        raise ValueError("anchor channels exceed padded channel count")
    if row_policy not in ("zero", "replicate"):
        raise ValueError(f"row_policy {row_policy!r} not in ('zero', 'replicate')")
    out_dtype = out_dtype or x_stream.dtype
    compute_dtype = compute_dtype or x_stream.dtype
    mask_rows = row_bounds is not None
    if not mask_rows:  # full-band validity placeholder (kernel ignores it)
        row_bounds = jnp.broadcast_to(jnp.array([0, R], jnp.int32), (B, 2))
    # Whole into SMEM, flat: a (1, 2) block of a (B, 2) array breaks the
    # TPU's (8, 128) block rule, and 1-D SMEM pads least.
    row_bounds = row_bounds.astype(jnp.int32).reshape(2 * B)

    kernel = functools.partial(
        tilted_fusion_kernel,
        num_layers=L,
        width=width,
        tile_cols=C,
        band_rows=R,
        chp=chp,
        c0p=c0p,
        relu_flags=tuple(relu_flags),
        add_anchor=add_anchor,
        in_channels=in_channels,
        anchor_repeats=anchor_repeats,
        row_policy=row_policy,
        mask_rows=mask_rows,
        compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((1, R, 1, c0p), lambda bnd, k: (bnd, 0, 0, 0)),
            pl.BlockSpec((1, R, C, c0p), lambda bnd, k: (bnd, 0, k, 0)),
            pl.BlockSpec((L, 3, 3, chp, chp), lambda bnd, k: (0, 0, 0, 0, 0)),
            pl.BlockSpec((L, chp), lambda bnd, k: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, R, C, chp), lambda bnd, k: (bnd, 0, k, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, KC, chp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM(shape, compute_dtype)
            for shape in scratch_shapes(L, R, C, chp, c0p)
        ],
        interpret=interpret,
        name="tilted_fusion",
    )(first_col, x_stream, w, b, row_bounds)
