"""Engine serving throughput: frames/s, dispatch/complete latency, cache.

The measurement the serving API exists for: batched requests stream
through an ``SRSession``, whose plan cache compiles ONE executor per
(plan, batch bucket, dtype) over a device-resident PreparedStack — so
throughput scales with batch size and repeat requests are pure cache hits.

Two serving modes are measured on the same multi-bucket clip:

* ``sync``      — ``pipeline_depth=1``: every chunk blocks before the next
  dispatches (the pre-pipeline serving path).
* ``pipelined`` — ``pipeline_depth=2`` (double buffering): chunk *t+1* is
  staged and dispatched while *t* computes; blocking happens only when the
  pipeline is full and at the tail.

Outputs are asserted bit-exact across modes, and the record carries the
compiled executor's roofline terms (per-frame FLOPs / HBM bytes via
``engine.plan_cost``) to tie serving throughput back to the paper's
DRAM-traffic claim.

The ``server`` section measures the SRServer front door on a burst of
concurrent small requests:

* ``solo``      — each request submitted and resolved alone (every request
  dispatches its own bucket, the pre-server behavior).
* ``coalesced`` — the whole burst submitted before the first ``result()``,
  so the micro-batching scheduler packs all requests' frames into shared
  bucket-sized dispatches.

Per-request outputs are asserted bit-exact across the two modes; the
record keeps each mode's dispatch count and mean bucket fill ratio plus
the coalesced-vs-solo speedup.

The ``autotune`` section runs the roofline-guided schedule autotuner
(``engine.autotune``) per request batch size: candidates are pruned
analytically, survivors compiled + measured, and each config reports the
winning schedule, its predicted-vs-measured time (achieved fraction of
roofline) and the default-vs-tuned speedup (>= 1 by construction — the
schema checker fails CI if a tuned schedule ever regresses).  The
``pipeline`` section records the autotuner's ``tuned_depth`` verdict on
the sync-vs-pipelined question (depth 1 on CPU, where overlap buys
nothing).

    PYTHONPATH=src python benchmarks/engine_throughput.py            # CSV rows
    PYTHONPATH=src python benchmarks/engine_throughput.py --json    # + BENCH_engine.json
    PYTHONPATH=src python benchmarks/engine_throughput.py --quick   # CI smoke sizes

Also exposes ``rows()`` for the ``benchmarks/run.py`` harness.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import numpy as np

from repro.data.synthetic import sr_pair_batch
from repro.engine import SRServer, SRSession, bucket_batch, plan_cost
from repro.models.abpn import ABPNConfig, init_abpn

DEFAULT_BATCHES = (1, 4, 8)

# keys a BENCH_engine.json record must carry — checked by
# benchmarks/check_bench_schema.py (CI fails on drift)
RECORD_KEYS = (
    "bench", "backend", "precision", "vertical_policy", "lr_shape",
    "band_rows", "jax_backend", "platform", "batch", "cache", "pipeline",
    "roofline", "server", "autotune", "analysis", "sharding",
)
BATCH_KEYS = (
    "frames_per_s", "p50_ms", "p95_ms", "p99_ms", "mean_ms",
    "dispatch_mean_ms", "compile_s", "bucket", "batches",
)
PIPELINE_KEYS = (
    "clip_frames", "bucket", "chunks", "depth", "reps", "bit_exact",
    "sync", "pipelined", "speedup", "tuned_depth",
)
MODE_KEYS = (
    "frames_per_s", "p50_ms", "p99_ms", "mean_ms", "dispatch_mean_ms",
    "peak_inflight",
)
ROOFLINE_KEYS = (
    "batch", "flops", "hbm_bytes", "flops_per_frame", "hbm_bytes_per_frame",
    "weight_bytes_resident",
)
SERVER_KEYS = (
    "request_frames", "concurrent_requests", "reps", "solo", "coalesced",
    "speedup", "bit_exact",
)
SERVER_MODE_KEYS = (
    "frames_per_s", "dispatches_per_burst", "mean_fill_ratio", "bucket",
)
AUTOTUNE_KEYS = (
    "batches", "depths", "prune_ratio", "configs",
)
AUTOTUNE_CONFIG_KEYS = (
    "batch", "band_rows", "pipeline_depth", "bucket", "bucket_policy",
    "predicted_ms", "measured_ms", "default_ms", "achieved_fraction",
    "default_frames_per_s", "tuned_frames_per_s", "speedup",
    "candidates_total", "candidates_pruned",
)
# static-analysis gate outcome: per-checker finding counts + the verdict
ANALYSIS_KEYS = ("concurrency", "plan", "program", "clean")
ANALYSIS_SEVERITY_KEYS = ("error", "warning", "info")
# mesh-sharded serving scaling curve (benchmarks/sharding_scaling.py,
# run in a forced-multi-device subprocess); every point must be bit-exact
SHARDING_KEYS = (
    "device_count", "backend", "precision", "vertical_policy", "lr_shape",
    "frames", "reps", "points", "skipped",
)
SHARDING_POINT_KEYS = (
    "devices", "replicas", "band_shards", "frames_per_s", "scaling",
    "halo_bytes_per_frame", "replica_fill", "bit_exact",
)


def _session(layers, cfg, args_like) -> SRSession:
    return SRSession(
        layers,
        backend=args_like["backend"],
        precision=args_like["precision"],
        vertical_policy=args_like["vertical_policy"],
        band_rows=args_like["band_rows"],
        scale=cfg.scale,
        pipeline_depth=args_like.get("pipeline_depth", 2),
        autotune="off",  # bench sections measure DEFAULT schedules; the
        # autotune section is where tuned schedules are measured
    )


def measure_batches(layers, cfg, opts, batch_sizes, reps) -> tuple:
    """Serve ``reps`` requests per batch size through one session; return
    stats per size plus the session's compile-cache record."""
    session = _session(layers, cfg, opts)
    results = {}
    h, w = opts["height"], opts["width"]
    for bs in batch_sizes:
        session.reset_stats()
        frames, _ = sr_pair_batch(0, bs * reps, lr_shape=(h, w),
                                  scale=cfg.scale)
        for i in range(0, bs * reps, bs):
            session.upscale(frames[i : i + bs])
        s = session.stats()
        bucket = bucket_batch(bs)
        compile_s = next(
            e["compile_s"] for e in session.cache_stats()["entries"]
            if e["bucket"] == bucket
        )
        results[str(bs)] = {
            "frames_per_s": round(s["fps"], 2),
            "p50_ms": round(s["p50_ms"], 2),
            "p95_ms": round(s["p95_ms"], 2),
            "p99_ms": round(s["p99_ms"], 2),
            "mean_ms": round(s["mean_ms"], 2),
            "dispatch_mean_ms": round(s["dispatch_mean_ms"], 2),
            "compile_s": round(compile_s, 2),
            "bucket": bucket,
            "batches": s["batches"],
        }
    cache = session.cache_stats()
    cache["hit_rate"] = round(cache["hit_rate"], 4)
    for e in cache["entries"]:
        e["compile_s"] = round(e["compile_s"], 2)
    for st in cache["stacks"]:
        st["prepare_s"] = round(st["prepare_s"], 4)
    return results, cache


def measure_pipeline(layers, cfg, opts, *, bucket, chunks, reps) -> dict:
    """One ``chunks * bucket``-frame clip served end-to-end in sync
    (depth 1) vs pipelined (depth 2) mode; steady-state fps over ``reps``
    passes, outputs checked bit-exact."""
    h, w = opts["height"], opts["width"]
    n = bucket * chunks
    clip, _ = sr_pair_batch(1, n, lr_shape=(h, w), scale=cfg.scale)
    modes = (("sync", 1), ("pipelined", 2))
    out = {"clip_frames": n, "bucket": bucket, "chunks": chunks,
           "depth": dict(modes)["pipelined"], "reps": reps}
    results = {}
    for mode, depth in modes:
        session = _session(layers, cfg, {**opts, "pipeline_depth": depth})
        session.max_bucket = bucket
        hr = session.upscale(clip)  # compile pass (outside the stats)
        session.reset_stats()
        for _ in range(reps):
            hr = session.upscale(clip)
        s = session.stats()
        results[mode] = hr
        out[mode] = {
            "frames_per_s": round(s["fps"], 2),
            "p50_ms": round(s["p50_ms"], 2),
            "p99_ms": round(s["p99_ms"], 2),
            "mean_ms": round(s["mean_ms"], 2),
            "dispatch_mean_ms": round(s["dispatch_mean_ms"], 2),
            "peak_inflight": s["peak_inflight"],
        }
    out["bit_exact"] = bool(
        np.array_equal(np.asarray(results["sync"]),
                       np.asarray(results["pipelined"]))
    )
    out["speedup"] = round(
        out["pipelined"]["frames_per_s"] / max(out["sync"]["frames_per_s"], 1e-9),
        3,
    )
    # the autotuner's measured pass is the ARBITER of pipeline depth: its
    # bounded-inflight dispatch loop measures depths 1..2 head-to-head and
    # ties prefer the shallower pipeline — on CPU (where overlap buys
    # nothing and depth 2 holds an extra slab live) this selects depth 1
    from repro.engine.autotune import tune

    probe = _session(layers, cfg, opts)
    plan = probe.plan_for((h, w, cfg.in_channels))
    entry = tune(layers, plan, bucket, depths=(1, 2), chunks=chunks,
                 reps=reps, max_band_candidates=1)
    out["tuned_depth"] = int(entry.pipeline_depth)
    return out


def measure_server(layers, cfg, opts, *, req_frames, n_requests, reps) -> dict:
    """Coalesced vs solo serving of ``n_requests`` concurrent
    ``req_frames``-frame requests through an ``SRServer``.

    Solo resolves each request before submitting the next (every request
    pays its own bucket dispatch); coalesced submits the whole burst
    first, so the scheduler packs the burst into shared bucket-sized
    dispatches.  Outputs are checked bit-exact per request across modes.
    """
    h, w = opts["height"], opts["width"]
    total = req_frames * n_requests
    clip, _ = sr_pair_batch(2, total, lr_shape=(h, w), scale=cfg.scale)
    requests = [clip[i * req_frames:(i + 1) * req_frames]
                for i in range(n_requests)]
    out = {"request_frames": req_frames, "concurrent_requests": n_requests,
           "reps": reps}
    results = {}
    for mode in ("solo", "coalesced"):
        session = _session(layers, cfg, opts)
        session.max_bucket = bucket_batch(total)
        server = SRServer({"bench": session})

        def burst():
            if mode == "solo":
                return [server.submit(r).result() for r in requests]
            futs = [server.submit(r) for r in requests]
            return [f.result() for f in futs]

        burst()  # compile pass for this mode's bucket (outside the timing)
        before = server.scheduler_stats()
        t0 = time.perf_counter()
        for _ in range(reps):
            hrs = burst()
        dt = time.perf_counter() - t0
        after = server.scheduler_stats()
        dispatches = after["dispatches"] - before["dispatches"]
        real = after["frames_dispatched"] - before["frames_dispatched"]
        slots = after["slots_dispatched"] - before["slots_dispatched"]
        results[mode] = hrs
        out[mode] = {
            "frames_per_s": round(total * reps / dt, 2) if dt > 0 else 0.0,
            "dispatches_per_burst": dispatches / reps,
            "mean_fill_ratio": round(real / slots, 4) if slots else 0.0,
            "bucket": int(after["recent_dispatches"][-1]["bucket"]),
        }
    out["bit_exact"] = bool(all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(results["solo"], results["coalesced"])
    ))
    out["speedup"] = round(
        out["coalesced"]["frames_per_s"] / max(out["solo"]["frames_per_s"], 1e-9),
        3,
    )
    return out


def measure_autotune(layers, cfg, opts, *, batches, depths, reps) -> dict:
    """The autotuner section: per request batch, sweep the legal schedule
    space (roofline-pruned, then measured) and report the winner against
    the default schedule.

    ``predicted_ms`` is the winner's analytic roofline time;
    ``achieved_fraction`` is predicted/measured (how close the measured
    schedule runs to its roofline bound); ``speedup`` is default_ms /
    tuned_ms — >= 1 by construction (the default candidate is always
    measured, never pruned, and the winner never measures worse).
    """
    from repro.engine.autotune import tune
    from repro.engine.plan import SRPlan

    h, w = opts["height"], opts["width"]
    plan = SRPlan.from_request(
        (h, w, cfg.in_channels),
        num_layers=len(layers),
        band_rows=opts["band_rows"],
        vertical_policy=opts["vertical_policy"],
        backend=opts["backend"],
        precision=opts["precision"],
        scale=cfg.scale,
    )
    configs = []
    for batch in batches:
        entry = tune(layers, plan, batch, depths=depths, reps=reps)
        cands = entry.candidates
        configs.append({
            "batch": int(batch),
            "band_rows": entry.band_rows,
            "pipeline_depth": entry.pipeline_depth,
            "bucket": entry.bucket,
            "bucket_policy": entry.bucket_policy,
            "predicted_ms": round(entry.predicted_ms, 3),
            "measured_ms": round(entry.measured_ms, 3),
            "default_ms": round(entry.default_ms, 3),
            "achieved_fraction": round(
                entry.predicted_ms / max(entry.measured_ms, 1e-9), 4),
            "default_frames_per_s": round(1e3 / max(entry.default_ms, 1e-9), 2),
            "tuned_frames_per_s": round(1e3 / max(entry.measured_ms, 1e-9), 2),
            "speedup": round(entry.speedup, 3),
            "candidates_total": len(cands),
            "candidates_pruned": sum(c.pruned for c in cands),
        })
    return {
        "batches": [int(b) for b in batches],
        "depths": [int(d) for d in depths],
        "prune_ratio": 1.5,
        "configs": configs,
    }


def measure_sharding(*, quick: bool = False, devices: int = 8) -> dict:
    """The mesh-sharded serving scaling curve (the ``sharding`` section).

    On a TPU the sweep runs in THIS process over the visible chips: a
    child could not reach a chip this process already holds.  On the CPU,
    JAX has fixed this process's device list already, so a child pinned to
    the CPU (``JAX_PLATFORMS=cpu``) runs ``benchmarks/sharding_scaling.py``
    on forced host devices and its JSON record is adopted verbatim.
    """
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    if jax.default_backend() != "cpu":
        sys.path.insert(0, here)  # sharding_scaling.py sits beside this file
        from sharding_scaling import QUICK, measure_scaling

        return measure_scaling(**(QUICK if quick else {}))

    script = os.path.join(here, "sharding_scaling.py")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, script, "--json-only"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharding_scaling.py failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout)


def measure_analysis() -> dict:
    """The static-verification gate's outcome, recorded alongside the
    perf sections: per-checker finding counts by severity plus the
    ``clean`` verdict (``python -m repro.analysis --all`` on this exact
    tree).  A record with ``clean: false`` fails the schema check — perf
    numbers from a tree that violates its own static invariants are not
    comparable."""
    from repro.analysis.sweep import analysis_report

    return analysis_report()


def measure(
    *,
    backend: str = "tilted",
    precision: str = "fp32",
    vertical_policy: str = "zero",
    height: int = 120,
    width: int = 64,
    band_rows: int | None = None,
    batch_sizes=DEFAULT_BATCHES,
    reps: int = 4,
    pipe_bucket: int = 4,
    pipe_chunks: int = 4,
    srv_request_frames: int = 2,
    srv_requests: int = 4,
    tune_batches=(1, 3, 4),
    tune_depths=(1, 2),
    sharding_quick: bool = False,
    sharding_devices: int = 8,
) -> dict:
    """The full benchmark record: per-batch-size stats, the pipelined-vs-
    sync clip comparison, the server coalesced-vs-solo comparison, and the
    compiled executor's roofline terms."""
    cfg = ABPNConfig()
    layers = init_abpn(jax.random.PRNGKey(0), cfg)
    opts = {
        "backend": backend,
        "precision": precision,
        "vertical_policy": vertical_policy,
        "height": height,
        "width": width,
        "band_rows": band_rows,
    }
    batch, cache = measure_batches(layers, cfg, opts, batch_sizes, reps)
    pipeline = measure_pipeline(
        layers, cfg, opts, bucket=pipe_bucket, chunks=pipe_chunks, reps=reps
    )
    server = measure_server(
        layers, cfg, opts, req_frames=srv_request_frames,
        n_requests=srv_requests, reps=reps,
    )
    autotune = measure_autotune(
        layers, cfg, opts, batches=tune_batches, depths=tune_depths,
        reps=reps,
    )
    probe = _session(layers, cfg, opts)
    plan = probe.plan_for((height, width, cfg.in_channels))
    roofline = plan_cost(plan, layers, pipe_bucket)
    return {
        "bench": "engine_throughput",
        "backend": backend,
        "precision": precision,
        "vertical_policy": vertical_policy,
        "lr_shape": [height, width, cfg.in_channels],
        "band_rows": plan.band_rows,
        "jax_backend": jax.default_backend(),
        "platform": platform.platform(),
        "batch": batch,
        "cache": cache,
        "pipeline": pipeline,
        "server": server,
        "roofline": roofline,
        "autotune": autotune,
        "analysis": measure_analysis(),
        "sharding": measure_sharding(quick=sharding_quick,
                                     devices=sharding_devices),
    }


def rows():
    """Harness rows (kept small: batch 1 and 4, few reps)."""
    t0 = time.perf_counter()
    rec = measure(batch_sizes=(1, 4), reps=3, pipe_bucket=2, pipe_chunks=4,
                  tune_batches=(1, 3), sharding_quick=True)
    us = (time.perf_counter() - t0) * 1e6
    out = []
    for bs, r in rec["batch"].items():
        out.append((f"engine.throughput.b{bs}", us,
                    f"{r['frames_per_s']:.1f} frames/s, p50 {r['p50_ms']:.1f} ms "
                    f"({rec['backend']}/{rec['precision']})"))
    p = rec["pipeline"]
    out.append(("engine.pipeline.speedup", us,
                f"pipelined {p['pipelined']['frames_per_s']:.1f} vs sync "
                f"{p['sync']['frames_per_s']:.1f} frames/s "
                f"(x{p['speedup']:.2f}, bit_exact={p['bit_exact']})"))
    v = rec["server"]
    out.append(("engine.server.coalesce", us,
                f"coalesced {v['coalesced']['frames_per_s']:.1f} vs solo "
                f"{v['solo']['frames_per_s']:.1f} frames/s "
                f"(x{v['speedup']:.2f}, fill "
                f"{v['coalesced']['mean_fill_ratio']:.2f} vs "
                f"{v['solo']['mean_fill_ratio']:.2f}, "
                f"bit_exact={v['bit_exact']})"))
    for t in rec["autotune"]["configs"]:
        out.append((f"engine.autotune.b{t['batch']}", us,
                    f"tuned {t['tuned_frames_per_s']:.1f} vs default "
                    f"{t['default_frames_per_s']:.1f} frames/s "
                    f"(x{t['speedup']:.2f}, bucket {t['bucket']} "
                    f"{t['bucket_policy']}, depth {t['pipeline_depth']}, "
                    f"{t['achieved_fraction']:.0%} of roofline)"))
    for pt in rec["sharding"]["points"]:
        out.append((f"engine.sharding.r{pt['replicas']}s{pt['band_shards']}",
                    us,
                    f"{pt['frames_per_s']:.1f} frames/s on {pt['devices']} "
                    f"device(s) (x{pt['scaling']:.2f} vs 1, "
                    f"bit_exact={pt['bit_exact']})"))
    c = rec["cache"]
    out.append(("engine.plan_cache", us,
                f"{c['misses']} compiles, hit rate {c['hit_rate']:.2f}"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_engine.json next to this script's repo root")
    ap.add_argument("--json-path", default=None,
                    help="explicit output path for the JSON record")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes: tiny shapes, 2 batch sizes, 2 reps")
    ap.add_argument("--backend", default="tilted",
                    choices=["reference", "tilted", "kernel"])
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "int8"])
    ap.add_argument("--policy", default="zero",
                    choices=["zero", "halo", "replicate"],
                    help="vertical band boundary policy (all backends)")
    ap.add_argument("--height", type=int, default=120)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--band-rows", type=int, default=None,
                    help="band height (default: derived from --height)")
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--batches", type=int, nargs="+", default=list(DEFAULT_BATCHES))
    ap.add_argument("--pipe-bucket", type=int, default=4,
                    help="chunk size of the pipelined-vs-sync clip")
    ap.add_argument("--pipe-chunks", type=int, default=4,
                    help="chunks in the pipelined-vs-sync clip (>= 4 shows "
                         "steady-state overlap)")
    args = ap.parse_args()

    kw = dict(backend=args.backend, precision=args.precision,
              vertical_policy=args.policy,
              height=args.height, width=args.width,
              band_rows=args.band_rows,
              batch_sizes=tuple(args.batches), reps=args.reps,
              pipe_bucket=args.pipe_bucket, pipe_chunks=args.pipe_chunks)
    if args.quick:
        kw.update(height=24, width=16, batch_sizes=(1, 2), reps=2,
                  pipe_bucket=2, pipe_chunks=4,
                  srv_request_frames=1, srv_requests=2,
                  tune_batches=(1, 3), sharding_quick=True)
    rec = measure(**kw)
    print("name,us_per_call,derived")
    for bs, r in rec["batch"].items():
        print(f'engine.throughput.b{bs},{r["mean_ms"] * 1e3:.1f},'
              f'"{r["frames_per_s"]:.1f} frames/s p50 {r["p50_ms"]:.1f} ms '
              f'p99 {r["p99_ms"]:.1f} ms (bucket {r["bucket"]}, '
              f'compile {r["compile_s"]:.2f}s)"')
    p = rec["pipeline"]
    print(f'engine.pipeline.sync,{p["sync"]["mean_ms"] * 1e3:.1f},'
          f'"{p["sync"]["frames_per_s"]:.1f} frames/s on '
          f'{p["chunks"]}x{p["bucket"]} clip"')
    print(f'engine.pipeline.pipelined,{p["pipelined"]["mean_ms"] * 1e3:.1f},'
          f'"{p["pipelined"]["frames_per_s"]:.1f} frames/s '
          f'(x{p["speedup"]:.2f} vs sync, bit_exact={p["bit_exact"]}, '
          f'tuned_depth={p["tuned_depth"]})"')
    v = rec["server"]
    print(f'engine.server.solo,0.0,'
          f'"{v["solo"]["frames_per_s"]:.1f} frames/s, '
          f'{v["solo"]["dispatches_per_burst"]:.1f} dispatches/burst '
          f'(bucket {v["solo"]["bucket"]}, fill '
          f'{v["solo"]["mean_fill_ratio"]:.2f})"')
    print(f'engine.server.coalesced,0.0,'
          f'"{v["coalesced"]["frames_per_s"]:.1f} frames/s, '
          f'{v["coalesced"]["dispatches_per_burst"]:.1f} dispatches/burst '
          f'(bucket {v["coalesced"]["bucket"]}, fill '
          f'{v["coalesced"]["mean_fill_ratio"]:.2f}, '
          f'x{v["speedup"]:.2f} vs solo, bit_exact={v["bit_exact"]})"')
    r = rec["roofline"]
    print(f'engine.roofline.b{r["batch"]},0.0,'
          f'"{r["hbm_bytes_per_frame"] / 1e6:.2f} MB HBM/frame, '
          f'{r["flops_per_frame"] / 1e9:.2f} GFLOP/frame, '
          f'{r["weight_bytes_resident"] / 1e3:.1f} kB weights resident"')
    for t in rec["autotune"]["configs"]:
        print(f'engine.autotune.b{t["batch"]},{t["measured_ms"] * 1e3:.1f},'
              f'"tuned {t["tuned_frames_per_s"]:.1f} vs default '
              f'{t["default_frames_per_s"]:.1f} frames/s '
              f'(x{t["speedup"]:.2f}, bucket {t["bucket"]} '
              f'{t["bucket_policy"]}, depth {t["pipeline_depth"]}, band '
              f'{t["band_rows"]}, {t["achieved_fraction"]:.0%} of roofline, '
              f'{t["candidates_pruned"]}/{t["candidates_total"]} pruned)"')
    for pt in rec["sharding"]["points"]:
        print(f'engine.sharding.r{pt["replicas"]}s{pt["band_shards"]},0.0,'
              f'"{pt["frames_per_s"]:.1f} frames/s on {pt["devices"]} '
              f'device(s) (x{pt["scaling"]:.2f} vs 1 device, '
              f'{pt["halo_bytes_per_frame"] / 1e3:.1f} kB halo/frame, '
              f'fill {pt["replica_fill"]:.2f}, bit_exact={pt["bit_exact"]})"')
    for s in rec["sharding"]["skipped"]:
        print(f'# sharding skipped ({s["replicas"]}x{s["band_shards"]}): '
              f'{s["reason"]}')
    c = rec["cache"]
    print(f'engine.plan_cache,0.0,"{c["misses"]} compiles {c["hits"]} hits '
          f'hit rate {c["hit_rate"]:.2f}"')
    if args.json or args.json_path:
        if args.json_path:
            path = args.json_path
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            path = os.path.join(root, "BENCH_engine.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
