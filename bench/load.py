"""What every traffic loop shares: the seeded LR frames, the record of each
request, the window, and the loop found by name.

A traffic mix is a data file, ``bench/traffic/<mix>.json``.  Its ``loop``
names the generator that reads it, ``bench/loops/<loop>.py``, which
exposes ``warm``, ``run`` and ``counts`` (see ``bench/loops/open.py``).
Its optional ``server`` keys are passed to ``SRServer.open`` over the
configuration's own (admission, queue bounds, ...), and ``refusals``
names the errors by which the server may rightly turn a request away
(they count as failed, and not against ``correct``).  Host spans
(``bench.*``) mark what the generator and the drain thread do, for the
trace's gap attribution.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import spec

DRAIN_IDLE_S = 0.05  # a drain turn at least this often, woken or not
GRACE_S = 60.0  # a frame not done this long after the window closes never comes


def _lerp_matrix(n: int, cell: int) -> np.ndarray:
    """(n, n // cell + 2) weights of linear interpolation over a coarse grid."""
    pos = np.arange(n, dtype=np.float32) / cell
    i0 = pos.astype(np.int64)
    m = np.zeros((n, n // cell + 2), np.float32)
    m[np.arange(n), i0] = 1 - (pos - i0)
    m[np.arange(n), i0 + 1] = pos - i0
    return m


def frame_pool(lr_shape, frames: int, seed: int) -> np.ndarray:
    """``(frames, H, W, C)`` float32 in [0, 1]: smooth noise (bilinear over
    an 8-pixel grid) plus a little white noise, from the seed."""
    h, w, c = lr_shape
    rng = np.random.default_rng(seed)
    cell = 8
    coarse = rng.random((frames, c, h // cell + 2, w // cell + 2), dtype=np.float32)
    smooth = _lerp_matrix(h, cell) @ coarse @ _lerp_matrix(w, cell).T
    pool = np.ascontiguousarray(smooth.transpose(0, 2, 3, 1))
    pool *= 0.85
    pool += 0.15 * rng.random(pool.shape, dtype=np.float32)
    return pool


def shapes(mix: dict, cfg: dict) -> list:
    """The LR frame shapes a mix sends: each group's ``lr_shape``, or the
    configuration's."""
    return sorted({tuple(g.get("lr_shape", cfg["lr_shape"]))
                   for g in mix.get("groups", [mix])})


def pools(mix: dict, cfg: dict, seed: int) -> dict:
    """``{lr_shape: frame pool}``, ``pool_frames`` seeded frames per shape."""
    return {s: frame_pool(s, mix["pool_frames"], seed + k)
            for k, s in enumerate(shapes(mix, cfg))}


@dataclasses.dataclass
class Request:
    due: float  # monotonic s: scheduled arrival (open) or submit (closed)
    frames: int
    shape: tuple  # LR frame shape, the key of its pool
    pool_index: int  # first pool frame of the request
    sample: bool  # some of its output is kept for the check
    done: Optional[float] = None
    error: Optional[BaseException] = None
    kept: list = dataclasses.field(default_factory=list)  # [(pool index, HR of the frames from it)]


@dataclasses.dataclass
class Window:
    requests: List[Request]
    start: float  # monotonic bounds of the measured window
    end: float
    send_lag_s: List[float] = dataclasses.field(default_factory=list)

    def due(self) -> List[Request]:
        """Every request scheduled (or submitted) inside the window."""
        return [r for r in self.requests if self.start <= r.due < self.end]


class Hooks:
    """What the harness does at the window's edges (reset counters, trace)."""

    def window_started(self) -> None:
        pass

    def trace_from(self, t: float) -> None:
        """Start tracing at monotonic time ``t`` (called once, early)."""

    def window_ended(self) -> None:
        pass


def sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def keep(hr, positions) -> list:
    """The frames of a served clip that the check compares, each
    ``(1, H, W, C)``; the clip itself is dropped, as a client would once it
    has used it."""
    kept = [hr[p:p + 1] for p in positions]
    for k in kept:
        k.block_until_ready()
    return kept


def warm_buckets(server, pools: dict, max_bucket: int) -> list:
    """Serve queues of 1, 2, 4.. single frames of every shape, so each
    dispatch bucket compiles before the window; returns what was warmed."""
    warmed = []
    for shape, pool in pools.items():
        b = 1
        while b <= max_bucket:
            futs = [server.submit(pool[i % len(pool)]) for i in range(b)]
            server.flush()
            for fut in futs:
                fut.result()
            warmed.append((shape, b))
            b *= 2
    return warmed


class Drainer:
    """A thread that turns the server over, as a serving worker would: it
    sleeps until a submit wakes it (``kick``), then drains everything
    queued and in flight.  Waking on work, not polling, leaves the host's
    cores and the interpreter lock to the generator and the runtime."""

    def __init__(self, server):
        self._server = server
        self._stop = threading.Event()
        self._work = threading.Event()
        self._thread = threading.Thread(target=self._drain, name="bench-drain")

    def kick(self) -> None:
        """There is new work: drain it."""
        self._work.set()

    def _drain(self) -> None:
        while not self._stop.is_set():
            self._work.wait(DRAIN_IDLE_S)
            self._work.clear()  # a submit after this wakes the next turn
            with TraceAnnotation("bench.flush"):
                self._server.flush()

    def __enter__(self) -> "Drainer":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._work.set()
        self._thread.join()


def loop(name: str):
    """The generator module ``bench/loops/<name>.py``."""
    return spec.load_module(spec.BENCH_DIR / "loops" / f"{name}.py", f"bench_loop_{name}")
