"""The rate sweep that fixes a live cell's stream count: the open-loop mix
at 1, 2, 3, ... streams on one warm server, one short window each.

    python3 bench/sweep.py --workload abpn_x3_1080p.live_60fps \\
        --streams 1,2,3,4 --seconds 5

Every group of the mix gets that many streams.  A rate is sustained when the backlog does not grow: the mean latency of
the window's last fifth stays near that of its first fifth.  Prints one
line per point.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", default="1,2,3,4")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=4_000_000_000)
    args = ap.parse_args()

    import tempfile

    import jax
    import numpy as np

    from bench import harness, load, spec, work

    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    harness.require_chip(jax, wl["chips"])
    harness.use_compile_cache(jax, harness.CACHE_DIR)
    ref = spec.reference_module(cfg)
    gen = load.loop(mix["loop"])
    options = harness.server_options(cfg, mix, None)
    weights = ref.init_weights(work.channels(cfg), args.seed)
    pools = load.pools(mix, cfg, args.seed)
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        server = harness.open_server(cfg, options, weights, tmp)
        gen.warm(server, pools, mix, options["max_bucket"])
        for s in (int(x) for x in args.streams.split(",")):
            point = {**mix, "groups": [{**g, "streams": s} for g in mix["groups"]],
                     "check_frames": 0}
            w = gen.run(server, pools, point, args.seconds, args.seed, load.Hooks(), None,
                        tuple(cfg["lr_shape"]))
            due = w.due()
            lat = np.asarray([((r.done if r.done else w.end + load.GRACE_S) - r.due) * 1e3
                              for r in due])
            fifth = max(1, len(due) // 5)
            done = [r for r in due if r.done is not None and r.error is None]
            span = max(r.done for r in done) - w.start if done else float("nan")
            offered = s * sum(g["fps"] for g in mix["groups"])
            print(f"sweep streams={s} offered_fps={offered} frames={len(due)} "
                  f"completed_fps={len(done) / span!r} p50_ms={np.percentile(lat, 50)!r} "
                  f"p95_ms={np.percentile(lat, 95)!r} first_fifth_mean_ms="
                  f"{lat[:fifth].mean()!r} last_fifth_mean_ms={lat[-fifth:].mean()!r} "
                  f"max_lag_ms={max(w.send_lag_s) * 1e3!r}", flush=True)
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
