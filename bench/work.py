"""The work each frame needs, from shapes only, and the chip's peaks.

Counts what the model requires, not what the compiler emits: no padded
lanes, no recomputed halo rows, no bias adds or ReLUs (a few FLOP per
output value against 9·Ci MACs).
"""

from __future__ import annotations

import json

from bench.spec import BENCH_DIR

F32_BYTES = 4


def channels(cfg: dict) -> list:
    """F_0..F_L channel counts of a plain conv-chain SR model."""
    return list(cfg["conv_channels"])


def flops_per_lr_pixel(ch: list) -> int:
    """2 FLOP per multiply-add of every 3x3 conv, per LR pixel."""
    return 2 * sum(9 * ci * co for ci, co in zip(ch, ch[1:]))


def weight_bytes(ch: list) -> int:
    return F32_BYTES * sum(9 * ci * co + co for ci, co in zip(ch, ch[1:]))


def flops_per_frame(cfg: dict, lr_shape=None) -> int:
    h, w, _ = lr_shape or cfg["lr_shape"]
    return flops_per_lr_pixel(channels(cfg)) * h * w


def frame_bytes(cfg: dict, lr_shape=None) -> tuple:
    """(LR bytes in, HR bytes out) of one fp32 frame."""
    h, w, c = lr_shape or cfg["lr_shape"]
    s = cfg["scale"]
    return F32_BYTES * h * w * c, F32_BYTES * h * w * c * s * s


def least_bytes(cfg: dict, frames: int, dispatches: int) -> int:
    """Least HBM traffic: every LR frame read once, every HR frame written
    once, the weights read once per dispatch."""
    lr, hr = frame_bytes(cfg)
    return frames * (lr + hr) + dispatches * weight_bytes(channels(cfg))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unlisted kind is an
    error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json "
                       f"(listed: {sorted(table)})")
    return table[device_kind]
