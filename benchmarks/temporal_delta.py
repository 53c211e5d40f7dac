"""Temporal delta serving benchmark: reuse vs motion, bit-exact splice.

Streams three synthetic clips through a :class:`DeltaSession` on one
shared session and records, per clip, how much conv-stack compute the
delta path actually ran:

* ``static``      — a static camera: every frame after the first is
  byte-identical, so only frame 0 dispatches and the compute reduction
  equals the clip length (band-rows served collapse to one frame's).
* ``panning``     — a small patch walks down one band per frame over a
  static background: the dirty set is the changed bands dilated by the
  halo reach, so a sliver of the frame recomputes each step.
* ``full_motion`` — fresh noise every frame: nothing can be reused and
  the delta path degenerates to full re-upscale (reduction 1.0) — the
  honest lower bound, recorded so the static number has a denominator.

Every delta-served frame is compared against ``session.upscale`` on the
same frame — the ``bit_exact`` flag per clip is the splice guarantee,
measured, not assumed.  The ``acceptance`` block pins the headline
claim CI gates on: the static clip's compute reduction is at least
``MIN_STATIC_COMPUTE_REDUCTION`` (4x) and every clip is bit-exact.

    PYTHONPATH=src python benchmarks/temporal_delta.py \\
        --json-path BENCH_temporal.json             # full record
    PYTHONPATH=src python benchmarks/temporal_delta.py --quick

Schema key tuples live here, next to the producer;
``check_bench_schema.py`` imports them so producer and checker cannot
drift apart.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.engine import SRServer, SRSession
from repro.engine.temporal import DeltaSession, halo_reach
from repro.models.abpn import ABPNConfig, init_abpn

# --- the committed schema (imported by check_bench_schema.py) ----------
TEMPORAL_RECORD_KEYS = (
    "bench", "jax_backend", "platform", "lr_shape", "band_rows",
    "bands_per_frame", "halo_reach", "backend", "vertical_policy",
    "precision", "frames_per_clip", "quick", "seed", "clips",
    "acceptance",
)
TEMPORAL_CLIP_KEYS = (
    "clip", "frames", "bands_total", "bands_served", "bands_skipped",
    "reuse_ratio", "band_rows_total", "band_rows_served",
    "compute_reduction", "band_dispatches",
    "effective_hbm_bytes_per_frame", "full_hbm_bytes_per_frame",
    "hbm_reduction", "bit_exact", "cache",
)
TEMPORAL_CACHE_KEYS = ("hits", "misses", "puts", "evictions", "bytes_saved")
TEMPORAL_ACCEPTANCE_KEYS = (
    "min_static_compute_reduction", "static_compute_reduction",
    "static_ok", "all_bit_exact",
)

# the headline floor: a static clip must cut conv-stack band-rows by at
# least this factor vs re-upscaling every frame
MIN_STATIC_COMPUTE_REDUCTION = 4.0

FULL_SHAPE = (64, 32, 3)
QUICK_SHAPE = (32, 32, 3)
BAND_ROWS = 8


def make_clips(shape, frames: int, band_rows: int, seed: int) -> dict:
    """The three motion regimes, as lists of float32 (H, W, C) frames.
    Distinct seeds per clip keep cross-clip cache hits out of the data."""
    h, w, c = shape
    patch = band_rows  # one band tall: the panning object crosses bands
    rng = np.random.default_rng(seed)
    base = rng.random(shape, dtype=np.float32)
    static = [base.copy() for _ in range(frames)]

    rng = np.random.default_rng(seed + 1)
    pan_bg = rng.random(shape, dtype=np.float32)
    panning = []
    for t in range(frames):
        f = pan_bg.copy()
        r0 = (t * band_rows) % (h - patch)
        f[r0:r0 + patch, : w // 2] += 0.25
        panning.append(f)

    rng = np.random.default_rng(seed + 2)
    full_motion = [rng.random(shape, dtype=np.float32) for _ in range(frames)]
    return {"static": static, "panning": panning,
            "full_motion": full_motion}


def run_clip(session, server, name: str, clip) -> dict:
    """Serve one clip through a fresh DeltaSession; counters are the
    session-level temporal counts diffed across the clip, so the record
    is immune to what earlier clips (or the oracle calls) did."""
    before = dict(session._temporal_counts)
    dispatches_before = session._band_dispatches
    cache_before = dict(session.output_cache().stats())

    exact = True
    with DeltaSession(session, server=server) as ds:
        for frame in clip:
            out = ds.serve(frame)
            ref = np.asarray(session.upscale(frame))
            exact = exact and np.array_equal(out, ref)

    t = session._temporal_counts
    d = {k: t[k] - before[k] for k in t}
    cache = session.output_cache().stats()
    frames = d["frames"]
    total = d["bands_total"]
    served = total - d["bands_skipped"]
    rows_served = d["band_rows_served"]
    return {
        "clip": name,
        "frames": frames,
        "bands_total": total,
        "bands_served": served,
        "bands_skipped": d["bands_skipped"],
        "reuse_ratio": round(d["bands_skipped"] / total, 4) if total else 0.0,
        "band_rows_total": d["band_rows_total"],
        "band_rows_served": rows_served,
        "compute_reduction": round(
            d["band_rows_total"] / rows_served, 3) if rows_served else None,
        "band_dispatches": session._band_dispatches - dispatches_before,
        "effective_hbm_bytes_per_frame": round(
            d["hbm_bytes_served"] / frames, 1) if frames else 0.0,
        "full_hbm_bytes_per_frame": round(
            d["hbm_bytes_full"] / frames, 1) if frames else 0.0,
        "hbm_reduction": round(
            d["hbm_bytes_full"] / d["hbm_bytes_served"], 3)
        if d["hbm_bytes_served"] else None,
        "bit_exact": bool(exact),
        "cache": {k: cache[k] - cache_before[k] for k in TEMPORAL_CACHE_KEYS},
    }


def measure(*, quick: bool, seed: int) -> dict:
    shape = QUICK_SHAPE if quick else FULL_SHAPE
    frames = 6 if quick else 8
    policy = "halo"  # non-trivial dilation: reach = ceil(L / R) bands

    cfg = ABPNConfig()
    layers = init_abpn(jax.random.PRNGKey(seed), cfg)
    session = SRSession(layers, backend="tilted", vertical_policy=policy,
                        band_rows=BAND_ROWS, autotune="off")
    clips = make_clips(shape, frames, BAND_ROWS, seed)
    with SRServer({"abpn_x3": session}) as server:
        results = [run_clip(session, server, name, clip)
                   for name, clip in clips.items()]

    by_name = {r["clip"]: r for r in results}
    static_red = by_name["static"]["compute_reduction"]
    acceptance = {
        "min_static_compute_reduction": MIN_STATIC_COMPUTE_REDUCTION,
        "static_compute_reduction": static_red,
        "static_ok": (static_red is not None
                      and static_red >= MIN_STATIC_COMPUTE_REDUCTION),
        "all_bit_exact": all(r["bit_exact"] for r in results),
    }
    return {
        "bench": "temporal_delta",
        "jax_backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "lr_shape": list(shape),
        "band_rows": BAND_ROWS,
        "bands_per_frame": shape[0] // BAND_ROWS,
        "halo_reach": halo_reach(BAND_ROWS, cfg.num_layers, policy),
        "backend": "tilted",
        "vertical_policy": policy,
        "precision": "fp32",
        "frames_per_clip": frames,
        "quick": quick,
        "seed": seed,
        "clips": results,
        "acceptance": acceptance,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes: smaller frames, shorter clips")
    ap.add_argument("--json-path", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rec = measure(quick=args.quick, seed=args.seed)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")

    print(f"delta serving {tuple(rec['lr_shape'])} x "
          f"{rec['frames_per_clip']} frames, band_rows {rec['band_rows']} "
          f"({rec['bands_per_frame']} bands, halo reach "
          f"{rec['halo_reach']}), {rec['backend']}/{rec['vertical_policy']}")
    for r in rec["clips"]:
        print(f"  {r['clip']:>11}: served {r['bands_served']:>3}/"
              f"{r['bands_total']:>3} bands (reuse {r['reuse_ratio']:.2f}), "
              f"compute x{r['compute_reduction']} fewer band-rows, "
              f"hbm x{r['hbm_reduction']}, bit_exact={r['bit_exact']}")
    acc = rec["acceptance"]
    print(f"acceptance: static compute reduction "
          f"x{acc['static_compute_reduction']} "
          f"(>= x{acc['min_static_compute_reduction']}: {acc['static_ok']}), "
          f"all clips bit-exact: {acc['all_bit_exact']}")
    return 0 if acc["static_ok"] and acc["all_bit_exact"] else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    sys.exit(main())
