"""Open loop: cameras that send whatever the server does (live video).

The mix lists ``groups`` of streams.  Each group has ``streams`` streams
and ``fps``; optional are ``frames`` per request (1), ``lr_shape`` (the
configuration's), ``start_s`` and ``stop_s`` (when its streams join and
leave the window: bursts), and ``submit`` keys passed to
``SRServer.submit`` (``priority``, ``timeout``).  ``check_frames``
requests are sampled for the check.

Latency is timed from the SCHEDULED arrival, so a stall is charged to
every request queued behind it.  Stream phases are drawn once from a
fixed seed, the same for every run seed; the run seed only deals the
phases to the streams and picks the frames, so every seed offers the same
arrivals.  A drain thread turns the server over.  The core (schedule
drawn before the run, latency from the scheduled arrival, a drain thread
calling ``flush()``) is copied from ``benchmarks/server_load.py``; here
each submit wakes the drain thread.
"""

from __future__ import annotations

import time
from typing import Callable, List

import numpy as np
from jax.profiler import TraceAnnotation

from bench import load

LEAD_S = 0.05  # first arrival this long after start
PHASE_SEED = 0


def warm(server, pools: dict, mix: dict, max_bucket: int) -> list:
    return load.warm_buckets(server, pools, max_bucket)


def schedule(mix: dict, pools: dict, default_shape, seconds: float,
             rng: np.random.Generator) -> list:
    """Sorted ``[(at s, group, shape, first pool index)]`` of the window."""
    sched = []
    for gi, g in enumerate(mix["groups"]):
        n, period, f = g["streams"], 1.0 / g["fps"], g.get("frames", 1)
        shape = tuple(g.get("lr_shape", default_shape))
        starts = len(pools[shape]) - f + 1
        phases = np.random.default_rng([PHASE_SEED, gi]).uniform(0, period, n)
        phases = phases[rng.permutation(n)]
        first = rng.integers(0, starts, size=n)
        t0, t1 = g.get("start_s", 0.0), min(g.get("stop_s", seconds), seconds)
        for i in range(n):
            k = 0
            while t0 + phases[i] + k * period < t1:
                sched.append((float(t0 + phases[i] + k * period), gi, shape,
                              int((first[i] + k) % starts)))
                k += 1
    return sorted(sched)


def run(server, pools: dict, mix: dict, seconds: float, seed: int,
        hooks: load.Hooks, trace_s, default_shape) -> load.Window:
    rng = np.random.default_rng(seed)
    sched = schedule(mix, pools, default_shape, seconds, rng)
    sample = set(int(i) for i in rng.choice(
        len(sched), size=min(mix["check_frames"], len(sched)), replace=False))
    groups = mix["groups"]

    def on_done(req: load.Request) -> Callable:
        def cb(fut):
            req.done = time.monotonic()
            req.error = fut.exception()
            if req.sample and req.error is None:
                # kept as served: an op on it here would compile in the window
                req.kept = [(req.pool_index, fut.result())]
        return cb

    requests: List[load.Request] = []
    lags: List[float] = []
    t0 = time.monotonic() + LEAD_S
    with load.Drainer(server) as drainer:
        hooks.window_started()
        if trace_s is not None:
            hooks.trace_from(t0 + max(seconds - trace_s, 0.0))
        for k, (at, gi, shape, idx) in enumerate(sched):
            due = t0 + at
            with TraceAnnotation("bench.sleep"):
                load.sleep_until(due)
            lags.append(time.monotonic() - due)
            f = groups[gi].get("frames", 1)
            req = load.Request(due=due, frames=f, shape=shape, pool_index=idx,
                               sample=k in sample)
            requests.append(req)
            frames = pools[shape][idx] if f == 1 else pools[shape][idx:idx + f]
            try:
                with TraceAnnotation("bench.submit"):
                    fut = server.submit(frames, **groups[gi].get("submit", {}))
            except Exception as e:  # refused at the door: counts as failed
                req.error, req.done = e, time.monotonic()
                continue
            fut.add_done_callback(on_done(req))
            drainer.kick()
        with TraceAnnotation("bench.sleep"):
            load.sleep_until(t0 + seconds)
        hooks.window_ended()
        give_up = t0 + seconds + load.GRACE_S
        while any(r.done is None for r in requests) and time.monotonic() < give_up:
            time.sleep(0.005)
    return load.Window(requests=requests, start=t0, end=t0 + seconds, send_lag_s=lags)


def counts(window: load.Window) -> tuple:
    """(frames attempted, requests failed): every request due in the window."""
    due = window.due()
    return sum(r.frames for r in due), [r for r in due if r.error is not None or r.done is None]
