"""Pallas TPU kernel: single-layer vectorwise 3x3 convolution.

The standalone analogue of one PE-block pass (paper §III-B/D): an input
*column slab* is broadcast against the three weight columns and accumulated
along the diagonal — on the MXU this is three shifted matmuls
``(R*C, 3*Ci) @ (3*Ci, Co)`` (rows im2col'd), one per weight column, or
equivalently the 9-tap accumulation used here for symmetry with the fused
kernel.

Grid: one step per C-column output tile.  The input stays unblocked in VMEM
(whole band) because a single layer has no overlap state to carry — this
kernel exists as the layer-by-layer *baseline* datapath (the [11]/[12]
execution style the paper compares against) and as a unit-testable slice of
the fused kernel's math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["conv3x3_call"]


def _kernel(x_ref, w_ref, b_ref, o_ref, *, tile_cols, band_rows, relu, acc_dtype):
    C, R = tile_cols, band_rows
    k = pl.program_id(0)
    ci = x_ref.shape[-1]
    co = o_ref.shape[-1]
    # slab: rows already carry the +-1 zero-pad halo; columns sliced with halo
    slab = x_ref[:, pl.dslice(k * C, C + 2), :].astype(acc_dtype)  # (R+2, C+2, Ci)
    acc = jnp.zeros((R * C, co), acc_dtype)
    for dy in range(3):
        for dx in range(3):
            patch = slab[dy:dy + R, dx:dx + C, :]
            acc = acc + jax.lax.dot(
                patch.reshape(R * C, ci),
                w_ref[dy, dx].astype(acc_dtype),
                preferred_element_type=acc_dtype,
            )
    out = acc.reshape(R, C, co) + b_ref[...].astype(acc_dtype)[None, None, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def conv3x3_call(
    x: jax.Array,  # (R, W, Ci)
    w: jax.Array,  # (3, 3, Ci, Co)
    b: jax.Array,  # (Co,)
    *,
    tile_cols: int = 8,
    relu: bool = True,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """SAME-padded 3x3 conv over a band, tiled along columns."""
    R, W, Ci = x.shape
    Co = w.shape[-1]
    C = tile_cols
    K = -(-W // C)  # ceil
    # zero SAME padding: +-1 rows, left 1 col, right up to the tile grid
    xp = jnp.pad(x, ((1, 1), (1, K * C + 1 - W), (0, 0)))
    out = pl.pallas_call(
        functools.partial(
            _kernel, tile_cols=C, band_rows=R, relu=relu, acc_dtype=acc_dtype
        ),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((R + 2, K * C + 2, Ci), lambda k: (0, 0, 0)),
            pl.BlockSpec((3, 3, Ci, Co), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((Co,), lambda k: (0,)),
        ],
        out_specs=pl.BlockSpec((R, C, Co), lambda k: (0, k, 0)),
        out_shape=jax.ShapeDtypeStruct((R, K * C, Co), x.dtype),
        interpret=interpret,
    )(xp, w, b)
    return out[:, :W, :]
