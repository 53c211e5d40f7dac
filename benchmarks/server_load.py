"""Open-loop load harness for SRServer's traffic-hardening stack.

Drives the server with Poisson arrivals (exponential inter-arrival
times scheduled on the monotonic clock, so latency is measured from the
SCHEDULED arrival — no coordinated omission) over a mixed workload:
two hosted models at different resolutions, heavy-tailed clip lengths
(capped Pareto), and mixed priorities.  Each load point runs the same
offered rate through two server configurations:

* ``block``    — bounded queue, ``admission="block"``, no deadlines, no
  degradation: the pre-hardening server.  Under overload the backlog
  (and the submitter) grows without bound and tail latency explodes.
* ``hardened`` — ``admission="shed"`` + per-request deadlines +
  :class:`DegradePolicy` (bf16 -> half lookahead -> half buckets): the
  server sheds and expires what it cannot serve in time and degrades
  what it can, holding the SERVED tail inside the SLO.

Rates are expressed as multiples of a closed-loop calibrated capacity,
so the ladder means the same thing on any machine.  The record's
``acceptance`` block pins the headline claim CI gates on: at the
overload point the hardened server's p99 stays within the SLO while
the block server's does not — with shedding, deadline expiries, and at
least one degradation transition actually observed.

A fault-injection section (``FailureInjector`` threaded into the
server's launch path) proves blast-radius isolation: failing the k-th
dispatch fails exactly that dispatch's request; every other request
completes bit-exact and the server keeps serving afterwards.

    PYTHONPATH=src python benchmarks/server_load.py \\
        --json-path BENCH_server_load.json          # full record
    PYTHONPATH=src python benchmarks/server_load.py --quick
    PYTHONPATH=src python benchmarks/server_load.py --fault-smoke

Schema key tuples live here, next to the producer;
``check_bench_schema.py`` imports them so producer and checker cannot
drift apart.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import SRSession
from repro.engine.scheduler import (
    DeadlineExceededError,
    QueueFullError,
    RequestShedError,
)
from repro.engine.server import DegradePolicy, SRServer
from repro.models.abpn import ABPNConfig, init_abpn

# --- the committed schema (imported by check_bench_schema.py) ----------
LOAD_RECORD_KEYS = (
    "bench", "jax_backend", "platform", "lr_shapes", "slo_p99_ms",
    "duration_s", "seed", "calibration", "points", "acceptance",
    "fault_injection",
)
CALIBRATION_KEYS = (
    "capacity_fps", "capacity_rps", "mean_request_frames", "per_model",
)
LOAD_POINT_KEYS = ("offered_rate_rps", "load_factor", "block", "hardened")
LOAD_MODE_KEYS = (
    "offered", "completed", "shed", "rejected", "deadline_missed",
    "failed", "served_rate_rps", "p50_ms", "p99_ms", "degrade_level",
    "degrade_transitions", "degraded_requests", "elapsed_s",
)
ACCEPTANCE_KEYS = (
    "offered_rate_rps", "slo_p99_ms", "hardened_p99_ms", "block_p99_ms",
    "hardened_within_slo", "block_within_slo",
)
FAULT_KEYS = (
    "requests", "injected_failures", "failed_requests",
    "unaffected_completed", "neighbors_bit_exact", "served_after_failure",
)

FULL_SHAPES = {"sd": (12, 16, 3), "hd": (24, 32, 3)}
QUICK_SHAPES = {"sd": (12, 16, 3)}
MODEL_MIX = {"sd": 0.6, "hd": 0.4}

# queue bound, in max-bucket multiples.  Kept SHORT on purpose: frames
# already handed to a dispatch are expiry-immune, so a deep queue lets
# partially-served requests ride far past their deadline and blows the
# served tail out of the SLO even while shedding works
QUEUE_BOUND = 4


def _pow2s(cap: int):
    b, out = 1, []
    while b <= cap:
        out.append(b)
        b *= 2
    return out


class Workload:
    """Hosted sessions plus pre-generated clip pools for every
    (model, length) the sampler can emit — arrivals never pay array
    construction, and warmup can pre-compile every reachable
    (shape, bucket, dtype) executor."""

    def __init__(self, shapes: dict, *, max_bucket: int, seed: int):
        cfg = ABPNConfig()
        layers = init_abpn(jax.random.PRNGKey(0), cfg)
        self.layers = layers
        self.shapes = dict(shapes)
        self.max_bucket = max_bucket
        self.sessions = {
            name: SRSession(layers, backend="tilted", autotune="off",
                            max_bucket=max_bucket)
            for name in shapes
        }
        self.pools = {}
        key = jax.random.PRNGKey(seed)
        for name, shape in shapes.items():
            self.pools[name] = {}
            for n in range(1, max_bucket + 1):
                key, sub = jax.random.split(key)
                self.pools[name][n] = jax.random.uniform(sub, (n, *shape))
        names = [m for m in shapes]
        probs = np.array([MODEL_MIX.get(m, 1.0) for m in names])
        self._names, self._probs = names, probs / probs.sum()

    def sample(self, rng, count: int):
        """(model, n_frames, priority) for `count` arrivals: mixed
        models, capped-Pareto heavy-tail clip lengths, priorities 0-2."""
        models = rng.choice(self._names, size=count, p=self._probs)
        lengths = np.minimum(
            self.max_bucket, 1 + rng.pareto(1.1, size=count).astype(int))
        prios = rng.integers(0, 3, size=count)
        return list(zip(models.tolist(), lengths.tolist(), prios.tolist()))

    def mean_request_frames(self, rng) -> float:
        return float(np.mean([n for _, n, _ in self.sample(rng, 4096)]))


def warmup(work: Workload) -> None:
    """Compile every (model, bucket, dtype) executor the run can touch —
    including bf16, which the DegradePolicy's first ladder step switches
    live traffic onto."""
    with SRServer(work.sessions) as server:
        for name in work.sessions:
            for n in _pow2s(work.max_bucket):
                clip = work.pools[name][n]
                server.submit(clip, model=name).result()
                server.submit(jnp.asarray(clip, jnp.bfloat16),
                              model=name).result()


def calibrate(work: Workload, *, reps: int, probe_s: float, rng,
              seed: int) -> dict:
    """Capacity, measured the way the load points will spend it.

    Per-model CLOSED-loop request times (max-bucket clips, back to
    back) anchor the deadline/SLO budgets on the worst-case service
    time.  Capacity itself comes from a saturation probe: a BLOCK-mode
    server driven by a PACED open loop at several times the closed-loop
    estimate, with the drain thread running — i.e. exactly the baseline
    configuration the load points compare against, machinery overhead
    (submit, scheduling, GIL hand-offs) included.  Pacing matters: a
    submitter that spins flat-out starves the drain thread of the GIL
    and measures a capacity far below what paced traffic achieves,
    which would quietly turn every "load factor" downstream into a
    several-times-larger multiple than it claims.  The rate is
    over-driven enough that blocking admission, not the pacing, is the
    throughput governor, and the rate is read off a steady-state
    completion window."""
    per_model = {}
    with SRServer(work.sessions) as server:
        for name in work._names:
            clip = work.pools[name][work.max_bucket]
            server.submit(clip, model=name).result()  # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                server.submit(clip, model=name).result()
            per_model[name] = {
                "request_ms": round(
                    (time.perf_counter() - t0) * 1e3 / reps, 4),
                "frames": work.max_bucket,
            }
    mean_frames = work.mean_request_frames(rng)
    # closed-loop estimate (optimistic: per-request overhead at typical
    # clip sizes is ignored) — only used to pick the probe's over-drive
    # rate, never reported as capacity
    blended_ms_per_frame = sum(
        float(p) * per_model[name]["request_ms"] / work.max_bucket
        for name, p in zip(work._names, work._probs))
    est_rps = 1e3 / blended_ms_per_frame / mean_frames

    server = SRServer(work.sessions,
                      max_inflight_frames=QUEUE_BOUND * work.max_bucket,
                      admission="block")
    stop = threading.Event()

    def drain():
        while not stop.is_set():
            server.flush()
            stop.wait(0.0005)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    reqs = work.sample(np.random.default_rng(seed), 65536)
    done, done_lock = [], threading.Lock()
    i = 0
    t0 = time.monotonic()

    def make_cb(n):
        def cb(fut):
            t = time.monotonic()
            with done_lock:
                done.append((t - t0, n))
        return cb

    interval = 1.0 / (4.0 * est_rps)
    next_t = t0
    while True:
        now = time.monotonic()
        if now - t0 >= probe_s:
            break
        if now < next_t:
            time.sleep(next_t - now)
        next_t += interval
        model, n, _ = reqs[i % len(reqs)]
        i += 1
        server.submit(work.pools[model][n],
                      model=model).add_done_callback(make_cb(n))
    t_sub = time.monotonic() - t0
    server.flush()
    elapsed = time.monotonic() - t0
    stop.set()
    drainer.join()
    server.close()
    # steady-state window: skip the warm-in quarter and the post-submit
    # drain tail, both of which bias the rate downward
    lo = 0.25 * probe_s
    steady = [(t, n) for t, n in done if lo <= t <= t_sub]
    span = t_sub - lo
    if len(steady) >= 10 and span > 0:
        rps = len(steady) / span
        fps = sum(n for _, n in steady) / span
    else:  # pragma: no cover - degenerate probe, fall back to the mean
        rps = len(done) / elapsed
        fps = sum(n for _, n in done) / elapsed
    return {
        "capacity_fps": round(fps, 2),
        "capacity_rps": round(rps, 2),
        "mean_request_frames": round(mean_frames, 3),
        "per_model": per_model,
    }


def run_point(work: Workload, *, rate_rps: float, duration_s: float,
              mode: str, slo_ms: float, deadline_ms: float,
              policy_slo_ms: float, seed: int) -> dict:
    """One (offered rate, server configuration) measurement."""
    rng = np.random.default_rng(seed)
    bound = QUEUE_BOUND * work.max_bucket
    policy = None
    if mode == "hardened":
        # a LONG breach streak so transient jitter at moderate load
        # cannot walk the ladder down; sustained overload (a queue that
        # is simply always full) breaches every observation and gets
        # there within a couple of queue drains anyway
        policy = DegradePolicy(policy_slo_ms, alpha=0.2,
                               breach_steps=4, recover_steps=8)
        server = SRServer(work.sessions, max_inflight_frames=bound,
                          admission="shed", degrade=policy)
    else:
        server = SRServer(work.sessions, max_inflight_frames=bound,
                          admission="block")

    # pre-sample the whole arrival schedule (open loop: times are fixed
    # BEFORE the run; a slow server cannot slow the offered load down)
    n_arrivals = max(1, int(rate_rps * duration_s))
    at = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_arrivals))
    at = at[at <= duration_s]
    reqs = work.sample(rng, len(at))

    records, rec_lock = [], threading.Lock()
    stop = threading.Event()

    def drain():
        while not stop.is_set():
            server.flush()
            stop.wait(0.0005)

    def make_cb(sched):
        def cb(fut):
            end = time.monotonic()
            with rec_lock:
                records.append((sched, end, fut.exception()))
        return cb

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    rejected = 0
    t0 = time.monotonic()
    for arrival, (model, n, prio) in zip(at, reqs):
        delay = (t0 + arrival) - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        sched = t0 + arrival
        kw = {}
        if mode == "hardened":
            kw["deadline"] = sched + deadline_ms / 1e3
        try:
            fut = server.submit(work.pools[model][n], model=model,
                                priority=int(prio), **kw)
        except QueueFullError:
            rejected += 1
            continue
        fut.add_done_callback(make_cb(sched))
    server.flush()
    elapsed = time.monotonic() - t0
    stop.set()
    drainer.join()
    server.close()  # releases the sessions for the next configuration

    ok_lat, shed, missed, failed = [], 0, 0, 0
    for sched, end, exc in records:
        if exc is None:
            ok_lat.append((end - sched) * 1e3)
        elif isinstance(exc, RequestShedError):
            shed += 1
        elif isinstance(exc, DeadlineExceededError):
            missed += 1
        else:
            failed += 1
    dg = server.stats().get("degrade", {})
    return {
        "offered": len(at),
        "completed": len(ok_lat),
        "shed": shed,
        "rejected": rejected,
        "deadline_missed": missed,
        "failed": failed,
        "served_rate_rps": round(len(ok_lat) / max(elapsed, 1e-9), 2),
        "p50_ms": round(float(np.percentile(ok_lat, 50)), 3) if ok_lat
        else None,
        "p99_ms": round(float(np.percentile(ok_lat, 99)), 3) if ok_lat
        else None,
        "degrade_level": dg.get("level", 0),
        "degrade_transitions": len(dg.get("transitions", [])),
        "degraded_requests": dg.get("degraded_requests", 0),
        "elapsed_s": round(elapsed, 3),
    }


def run_fault_injection(work: Workload) -> dict:
    """Blast-radius proof: fail the k-th dispatch, show only that
    dispatch's request fails, neighbors stay bit-exact, and the server
    serves normally afterwards."""
    from repro.runtime.resilience import FailureInjector, InjectedFailure

    name = next(iter(work.sessions))
    # sessions of their own: the injector run must not pollute the load
    # sessions' stats, and max_bucket=2 pins one request per dispatch.
    # References come from a SEPARATE clean session — upscale() would
    # lazily bind an embedded server to whichever session it runs on.
    session = SRSession(work.layers, backend="tilted", autotune="off",
                        max_bucket=2)
    ref_session = SRSession(work.layers, backend="tilted", autotune="off",
                            max_bucket=2)
    refs = []
    clips = []
    key = jax.random.PRNGKey(7)
    for _ in range(4):
        key, sub = jax.random.split(key)
        clip = jax.random.uniform(sub, (2, *work.shapes[name]))
        clips.append(clip)
        refs.append(np.asarray(ref_session.upscale(clip)))

    injector = FailureInjector(fail_dispatches={1})
    server = SRServer({name: session}, injector=injector)
    futs = [server.submit(c, model=name) for c in clips]
    server.flush()

    failed, exact, completed = 0, True, 0
    for i, fut in enumerate(futs):
        exc = fut.exception()
        if isinstance(exc, InjectedFailure):
            failed += 1
        elif exc is None:
            completed += 1
            exact = exact and np.array_equal(np.asarray(fut.result()),
                                             refs[i])
        else:  # pragma: no cover - any other failure breaks isolation
            failed += 1
            exact = False
    after = server.submit(clips[0], model=name).result()
    return {
        "requests": len(futs),
        "injected_failures": injector.stats()["injected_failures"],
        "failed_requests": failed,
        "unaffected_completed": completed,
        "neighbors_bit_exact": bool(exact),
        "served_after_failure": bool(
            np.array_equal(np.asarray(after), refs[0])),
    }


def measure(*, quick: bool, seed: int) -> dict:
    shapes = QUICK_SHAPES if quick else FULL_SHAPES
    max_bucket = 2 if quick else 8
    duration_s = 1.0 if quick else 3.0
    load_factors = (0.5, 3.0) if quick else (0.5, 1.5, 4.0)
    reps = 20 if quick else 40

    rng = np.random.default_rng(seed)
    work = Workload(shapes, max_bucket=max_bucket, seed=seed)
    warmup(work)
    cal = calibrate(work, reps=reps, probe_s=0.5 if quick else 1.0,
                    rng=rng, seed=seed)

    # the slowest model's closed-loop request time anchors the budgets:
    # a deadline many services deep, an SLO with drain headroom above
    # the deadline.  The 30 ms floor sits above the host's background
    # scheduling jitter (OS preemption, allocator stalls — visible as
    # ~30 ms stragglers even in an underloaded block-mode server), so
    # a healthy load point does not expire requests over noise.
    t_req = max(m["request_ms"] for m in cal["per_model"].values())
    deadline_ms = max(12.0 * t_req, 30.0)
    # 3x the deadline: a request dispatched JUST inside its deadline is
    # expiry-immune from its first served frame on, so its completion
    # can trail the deadline by a queue-bound drain plus scheduling
    # jitter — the SLO needs that overhang as headroom
    slo_ms = 3.0 * deadline_ms
    # degrade trigger: at overload the bounded queue is ALWAYS full, so
    # every served request waits about one full-queue drain — while a
    # healthy queue is mostly empty and latency is a service time or
    # two.  0.8x the drain time splits those regimes at any scale; the
    # 0.55x-deadline floor keeps the trigger above background jitter
    # when the drain time itself is tiny (per-request overhead, not
    # frame count, dominates small-bucket queues)
    drain_ms = QUEUE_BOUND * max_bucket / cal["capacity_fps"] * 1e3
    policy_slo_ms = max(0.8 * drain_ms, 0.55 * deadline_ms)

    points = []
    for lf in load_factors:
        rate = lf * cal["capacity_rps"]
        point = {"offered_rate_rps": round(rate, 2), "load_factor": lf}
        for mode in ("block", "hardened"):
            point[mode] = run_point(
                work, rate_rps=rate, duration_s=duration_s, mode=mode,
                slo_ms=slo_ms, deadline_ms=deadline_ms,
                policy_slo_ms=policy_slo_ms, seed=seed + int(lf * 10))
        points.append(point)

    top = points[-1]
    acceptance = {
        "offered_rate_rps": top["offered_rate_rps"],
        "slo_p99_ms": round(slo_ms, 3),
        "hardened_p99_ms": top["hardened"]["p99_ms"],
        "block_p99_ms": top["block"]["p99_ms"],
        "hardened_within_slo": (
            top["hardened"]["p99_ms"] is not None
            and top["hardened"]["p99_ms"] <= slo_ms),
        "block_within_slo": (
            top["block"]["p99_ms"] is not None
            and top["block"]["p99_ms"] <= slo_ms),
    }
    return {
        "bench": "server_load",
        "jax_backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "lr_shapes": {m: list(s) for m, s in shapes.items()},
        "slo_p99_ms": round(slo_ms, 3),
        "duration_s": duration_s,
        "seed": seed,
        "calibration": cal,
        "points": points,
        "acceptance": acceptance,
        "fault_injection": run_fault_injection(work),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes: one model, short points")
    ap.add_argument("--json-path", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-smoke", action="store_true",
                    help="run ONLY the fault-injection isolation proof")
    args = ap.parse_args()

    if args.fault_smoke:
        work = Workload(QUICK_SHAPES, max_bucket=2, seed=args.seed)
        fi = run_fault_injection(work)
        print(json.dumps(fi, indent=2, sort_keys=True))
        ok = (fi["neighbors_bit_exact"] and fi["served_after_failure"]
              and fi["failed_requests"] == fi["injected_failures"] == 1)
        print(f"fault isolation: {'ok' if ok else 'BROKEN'}")
        return 0 if ok else 1

    rec = measure(quick=args.quick, seed=args.seed)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")

    cal = rec["calibration"]
    print(f"capacity: {cal['capacity_rps']} req/s "
          f"({cal['capacity_fps']} frames/s, "
          f"mean {cal['mean_request_frames']} frames/req); "
          f"SLO p99 <= {rec['slo_p99_ms']} ms")
    for p in rec["points"]:
        for mode in ("block", "hardened"):
            m = p[mode]
            print(f"  x{p['load_factor']:<4} {mode:>8}: "
                  f"offered {m['offered']:>4}  ok {m['completed']:>4}  "
                  f"shed {m['shed']:>3}  expired {m['deadline_missed']:>3}  "
                  f"p50 {m['p50_ms']} ms  p99 {m['p99_ms']} ms  "
                  f"degrade_level {m['degrade_level']}")
    acc = rec["acceptance"]
    fi = rec["fault_injection"]
    print(f"acceptance @ {acc['offered_rate_rps']} req/s: "
          f"hardened p99 {acc['hardened_p99_ms']} ms "
          f"(within SLO: {acc['hardened_within_slo']}), "
          f"block p99 {acc['block_p99_ms']} ms "
          f"(within SLO: {acc['block_within_slo']})")
    print(f"fault isolation: bit_exact={fi['neighbors_bit_exact']} "
          f"served_after={fi['served_after_failure']}")
    ok = acc["hardened_within_slo"] and not acc["block_within_slo"]
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    sys.exit(main())
