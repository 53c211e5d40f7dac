"""Closed loop: ``clients`` callers, each submitting a ``clip_frames``-frame
clip (of ``lr_shape``, by default the configuration's) and the next one
when it completes (offline upscaling).

The window opens at the first completion after a short lead-in and closes
at the first completion ``seconds`` later, so it holds whole requests
only.  ``check_requests`` requests, drawn from the seed among requests
``check_from`` .. ``check_from + check_span``, keep the frames at
``check_positions`` for the check.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import List

import numpy as np
from jax.profiler import TraceAnnotation

from bench import load

LEAD_IN_S = 0.5  # fill the pipeline before the window opens


def warm(server, pools: dict, mix: dict, max_bucket: int) -> list:
    f = mix["clip_frames"]
    for pool in pools.values():
        futs = [server.submit(pool[i:i + f]) for i in range(mix["clients"])]
        for fut in futs:
            load.keep(fut.result(), mix["check_positions"])
    return [(shape, min(f, max_bucket)) for shape in pools]


def run(server, pools: dict, mix: dict, seconds: float, seed: int,
        hooks: load.Hooks, trace_s, default_shape) -> load.Window:
    f, positions = mix["clip_frames"], mix["check_positions"]
    shape = tuple(mix.get("lr_shape", default_shape))
    pool = pools[shape]
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, len(pool) - f + 1, size=4096)
    lo, span = mix["check_from"], mix["check_span"]
    check = set(int(i) for i in rng.choice(np.arange(lo, lo + span),
                                           size=mix["check_requests"], replace=False))
    requests: List[load.Request] = []
    cond = threading.Condition()
    stop = threading.Event()
    counter = itertools.count()

    def client():
        while not stop.is_set():
            i = next(counter)
            s = int(starts[i % len(starts)])
            req = load.Request(due=time.monotonic(), frames=f, shape=shape,
                               pool_index=s, sample=i in check)
            try:
                with TraceAnnotation("bench.submit"):
                    fut = server.submit(pool[s:s + f])
                with TraceAnnotation("bench.result"):
                    hr = fut.result()
                if req.sample:
                    req.kept = list(zip((s + p for p in positions), load.keep(hr, positions)))
                del hr, fut  # a client drops each clip once served
            except Exception as e:  # a failed request counts, the client goes on
                req.error = e
            req.done = time.monotonic()
            with cond:
                requests.append(req)
                cond.notify_all()

    def first_done_after(t: float) -> float:
        with cond:
            while True:
                later = [r.done for r in requests if r.done >= t]
                if later:
                    return min(later)
                cond.wait()

    threads = [threading.Thread(target=client, name=f"bench-client-{k}")
               for k in range(mix["clients"])]
    t_run = time.monotonic()
    for t in threads:
        t.start()
    try:
        start = first_done_after(t_run + LEAD_IN_S)
        hooks.window_started()
        if trace_s is not None:
            hooks.trace_from(start + max(seconds - trace_s, 0.0))
        end = first_done_after(start + seconds)
        hooks.window_ended()
    finally:
        stop.set()
        for t in threads:
            t.join()
    return load.Window(requests=requests, start=start, end=end)


def counts(window: load.Window) -> tuple:
    """(frames attempted, requests failed): the requests that ended in the
    window."""
    mine = [r for r in window.requests
            if r.done is not None and window.start < r.done <= window.end]
    return sum(r.frames for r in mine), [r for r in mine if r.error is not None]
