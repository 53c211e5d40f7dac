"""Sharded-serving scaling: frames/s from 1 device to an R x S mesh.

Serves the same clip through ``SRSession`` at a ladder of mesh topologies
— single device, band-sharded (1, S), and replicated + band-sharded
(R, S) — and records per-point throughput, the halo-exchange traffic the
topology implies, replica fill, and whether the output stayed bit-exact
vs the single-device baseline (the sharded executor's core guarantee;
the schema checker fails CI if any point breaks it).

The vertical policy defaults to ``halo`` because it is the one whose
output is independent of band geometry: topologies that force a re-banding
(``shardable_band_rows``) still compare bit-exact.  Points whose topology
does not fit the visible devices (or has no legal band decomposition) are
recorded under ``skipped``, never dropped silently.

On a TPU host the ladder runs over the visible chips.  On the CPU, JAX
must see the devices BEFORE it initialises, so run it with forced host
devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/sharding_scaling.py --json-only
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.data.synthetic import sr_pair_batch
from repro.engine import SRSession
from repro.engine.plan import shardable_band_rows
from repro.models.abpn import ABPNConfig, init_abpn

# the scaling ladder: single device -> band shards -> replicas x shards
DEFAULT_SPECS = ((1, 1), (1, 2), (1, 4), (2, 4))
# --quick: CI smoke sizes
QUICK = dict(height=48, width=16, frames=2, reps=2)


def measure_scaling(
    *,
    height: int = 120,
    width: int = 64,
    backend: str = "tilted",
    precision: str = "fp32",
    vertical_policy: str = "halo",
    frames: int = 4,
    reps: int = 3,
    specs=DEFAULT_SPECS,
) -> dict:
    cfg = ABPNConfig()
    layers = init_abpn(jax.random.PRNGKey(0), cfg)
    clip, _ = sr_pair_batch(0, frames, lr_shape=(height, width),
                            scale=cfg.scale)
    avail = jax.device_count()
    points, skipped = [], []
    base_fps = None
    want = None
    for replicas, band_shards in specs:
        needed = replicas * band_shards
        if needed > avail:
            skipped.append({"replicas": replicas, "band_shards": band_shards,
                            "reason": f"needs {needed} devices, "
                                      f"{avail} visible"})
            continue
        if band_shards > 1 and shardable_band_rows(height, band_shards) is None:
            skipped.append({"replicas": replicas, "band_shards": band_shards,
                            "reason": f"height {height} has no band "
                                      f"decomposition into {band_shards} "
                                      "shards"})
            continue
        mesh_kw = {} if needed == 1 else {"mesh": (replicas, band_shards)}
        session = SRSession(
            layers, backend=backend, precision=precision,
            vertical_policy=vertical_policy, scale=cfg.scale, **mesh_kw,
        )
        out = np.asarray(session.upscale(clip))  # compile pass
        if want is None:
            want = out
        bit_exact = bool(np.array_equal(out, want))
        session.reset_stats()
        for _ in range(reps):
            session.upscale(clip)
        fps = session.stats()["fps"]
        if base_fps is None:
            base_fps = fps
        sh = session.sharding_stats()
        points.append({
            "devices": needed,
            "replicas": replicas,
            "band_shards": band_shards,
            "frames_per_s": round(fps, 2),
            "scaling": round(fps / max(base_fps, 1e-9), 3),
            "halo_bytes_per_frame": (
                0 if sh is None else int(sh["halo_bytes_per_frame"])),
            "replica_fill": 0.0 if sh is None else round(sh["replica_fill"], 3),
            "bit_exact": bit_exact,
        })
    return {
        "device_count": avail,
        "backend": backend,
        "precision": precision,
        "vertical_policy": vertical_policy,
        "lr_shape": [height, width, cfg.in_channels],
        "frames": frames,
        "reps": reps,
        "points": points,
        "skipped": skipped,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes: tiny clip, 2 reps")
    ap.add_argument("--json-only", action="store_true",
                    help="emit ONLY the JSON record on stdout")
    ap.add_argument("--json-path", default=None)
    ap.add_argument("--height", type=int, default=120)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--backend", default="tilted",
                    choices=["tilted", "kernel"])
    ap.add_argument("--policy", default="halo",
                    choices=["zero", "halo", "replicate"])
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    kw = dict(height=args.height, width=args.width, backend=args.backend,
              vertical_policy=args.policy, frames=args.frames, reps=args.reps)
    if args.quick:
        kw.update(QUICK)
    rec = measure_scaling(**kw)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.json_only:
        print(json.dumps(rec, sort_keys=True))
        return
    print("name,us_per_call,derived")
    for p in rec["points"]:
        print(f'sharding.r{p["replicas"]}s{p["band_shards"]},0.0,'
              f'"{p["frames_per_s"]:.1f} frames/s on {p["devices"]} '
              f'device(s) (x{p["scaling"]:.2f} vs 1 device, '
              f'{p["halo_bytes_per_frame"] / 1e3:.1f} kB halo/frame, '
              f'fill {p["replica_fill"]:.2f}, '
              f'bit_exact={p["bit_exact"]})"')
    for s in rec["skipped"]:
        print(f'# skipped ({s["replicas"]}x{s["band_shards"]}): {s["reason"]}')


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
