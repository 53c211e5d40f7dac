"""ABPN — Anchor-based Plain Net (Du et al., CVPR-W 2021), the paper's model.

Seven layers (paper §III-A): six 3x3 convs with ReLU (3->28, then 28->28 x5)
and a final 3x3 conv to ``3 * scale**2`` channels followed by the
"residual-like structure" — the *anchor*: the input image replicated
``scale**2`` times per channel is added to the final conv output so the
network only learns the residual against a nearest-neighbour upsample; a
pixel shuffle (depth-to-space) then produces the HR image.

Execution is delegated to the batched engine subsystem (``repro.engine``):
build an ``SRPlan`` (backend ``reference`` | ``tilted`` | ``kernel``) and run
frame batches through one jitted call.  ``apply_abpn(method=...)`` remains as
a deprecated single-frame shim over that API.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.fusion import ConvLayer

__all__ = [
    "ABPNConfig",
    "init_abpn",
    "depth_to_space",
    "make_anchor",
    "apply_abpn",
    "param_count",
]


@dataclasses.dataclass(frozen=True)
class ABPNConfig:
    in_channels: int = 3
    feature_channels: int = 28  # paper: all intermediate layers have 28
    num_layers: int = 7
    scale: int = 3  # x3 SR: 640x360 -> 1920x1080
    clip: bool = True  # clip output to [0, 1] (8-bit image range)

    @property
    def out_channels(self) -> int:
        return self.in_channels * self.scale * self.scale

    @property
    def channels(self) -> List[int]:
        """F_0..F_L channel counts — feeds ``core.analysis.HWConfig``."""
        return (
            [self.in_channels]
            + [self.feature_channels] * (self.num_layers - 1)
            + [self.out_channels]
        )


def init_abpn(key: jax.Array, cfg: ABPNConfig = ABPNConfig(), dtype=jnp.float32) -> List[ConvLayer]:
    """He-initialised ABPN conv stack."""
    ch = cfg.channels
    layers = []
    for i in range(cfg.num_layers):
        key, wk = jax.random.split(key)
        ci, co = ch[i], ch[i + 1]
        fan_in = 9 * ci
        w = jax.random.normal(wk, (3, 3, ci, co), dtype) * jnp.sqrt(2.0 / fan_in)
        b = jnp.zeros((co,), dtype)
        layers.append(ConvLayer(w=w, b=b, relu=(i < cfg.num_layers - 1)))
    return layers


def depth_to_space(x: jax.Array, block: int) -> jax.Array:
    """(H, W, C*block^2) -> (H*block, W*block, C), channel-major blocks.

    Convention: ``out[y*b+dy, x*b+dx, c] = in[y, x, c*b*b + dy*b + dx]`` —
    chosen so that replicating each input channel ``b*b`` times yields an
    exact nearest-neighbour upsample (the ABPN anchor), which is tested.
    """
    H, W, CB = x.shape
    b = block
    C = CB // (b * b)
    if C * b * b != CB:
        raise ValueError(f"channels {CB} not divisible by block^2 {b * b}")
    x = x.reshape(H, W, C, b, b)
    x = x.transpose(0, 3, 1, 4, 2)  # H, dy, W, dx, C
    return x.reshape(H * b, W * b, C)


def make_anchor(lr: jax.Array, scale: int) -> jax.Array:
    """The ABPN anchor: each input channel repeated scale^2 times.

    ``depth_to_space(make_anchor(lr, s), s)`` == nearest-neighbour upsample.
    In the accelerator this is the residual SRAM path added in the second
    accumulator stage (paper §III-C); its buffer cost is eq. (3).
    """
    return jnp.repeat(lr, scale * scale, axis=-1)


def apply_abpn(
    layers: Sequence[ConvLayer],
    lr: jax.Array,
    cfg: ABPNConfig = ABPNConfig(),
    method: str = "reference",
    band_rows: int = 60,
    tile_cols: Optional[int] = None,
    vertical_policy: str = "zero",
) -> jax.Array:
    """LR (H, W, in_ch) -> HR (H*scale, W*scale, in_ch).

    .. deprecated::
        Thin shim over :mod:`repro.engine` kept for existing callers — it
        rebuilds an :class:`~repro.engine.SRPlan` per call and runs a
        single-frame batch.  New code should build a plan once with
        :func:`repro.engine.make_plan` and use :func:`repro.engine.run` /
        :class:`repro.engine.VideoStream` over frame batches instead.
    """
    from repro import engine  # local import: models must not hard-cycle engine

    if method not in ("reference", "tilted", "kernel"):
        raise ValueError(f"unknown method {method!r}")
    plan = engine.make_plan(
        layers,
        lr.shape,
        band_rows=band_rows,
        tile_cols=tile_cols,
        vertical_policy=vertical_policy,
        backend=method,
        scale=cfg.scale,
        clip=cfg.clip,
    )
    return engine.run(plan, layers, lr[None])[0]


def param_count(layers: Sequence[ConvLayer]) -> int:
    return sum(int(l.w.size + l.b.size) for l in layers)
