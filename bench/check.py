"""The comparison that decides ``correct``.

Every sampled HR frame the timed path produced is compared with the
configuration's plain reference, computed from the same LR frame and the
same seeded weights, after the window has closed and the program's
state is freed.  Two numbers, each held to its own limit from the
configuration's ``check`` table:

* ``max_gap``: the largest |HR - reference| over every value compared;
* ``frame_mean_gap``: the mean |HR - reference| of the worst frame, so
  one altered frame among many still shows.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import spec

REF_CHUNK_PIXELS = 360 * 640 * 4  # LR pixels per reference call: fits one chip


def gaps(cfg: dict, options: dict, weights, frames: dict) -> dict:
    """``max_gap`` and ``frame_mean_gap`` of HR frames against the reference
    on their LR frames, ``frames`` = ``{lr_shape: (LR, HR)}`` on the host,
    a few frames per call; ``options`` are the server's (band rows and
    vertical policy)."""
    ref = spec.reference_module(cfg)
    max_gap, frame_mean = 0.0, 0.0
    for (h, w, _), (lr, hr) in frames.items():
        chunk = max(1, REF_CHUNK_PIXELS // (h * w))
        for i in range(0, len(lr), chunk):
            want = np.asarray(ref.hr_frames(
                weights, jnp.asarray(lr[i:i + chunk]), scale=cfg["scale"],
                band_rows=options["band_rows"], policy=options["vertical_policy"],
                operands=cfg["matmul_operands"]))
            got = hr[i:i + chunk]
            if got.shape != want.shape:
                raise ValueError(f"HR shape {got.shape} != reference {want.shape}")
            diff = np.abs(got.astype(np.float32) - want)
            if not np.isfinite(diff).all():
                return {"max_gap": float("inf"), "frame_mean_gap": float("inf")}
            max_gap = max(max_gap, float(diff.max()))
            frame_mean = max(frame_mean, float(diff.reshape(len(diff), -1).mean(1).max()))
    return {"max_gap": max_gap, "frame_mean_gap": frame_mean}


def verdict(cfg: dict, numbers: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` in the configuration's order."""
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in cfg["check"].items()}


def passes(table: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in table.values())
