"""SRServer — the request/future serving front door over SRSessions.

``SRSession.upscale`` is caller-batched and blocking: every request pays
its own padded bucket and two concurrent half-bucket requests can never
share a dispatch.  ``SRServer`` moves admission and batching into the
engine, the way the block-streaming schedulers of ACNPU/BSRA own their
datapath's work queue:

* ``SRServer.open("abpn_x3", ...)`` hosts one or more named
  :class:`~repro.engine.session.SRSession`\\ s (multi-model traffic routes
  through each session's own ``PlanCache``/``PreparedStack`` machinery).
* ``server.submit(frames, model=..., priority=...)`` validates and queues
  a request and returns an :class:`SRFuture` immediately; requests that
  share a ``(model, plan, dtype)`` key are COALESCED by the
  :class:`~repro.engine.scheduler.MicroBatchScheduler` into bucket-sized
  dispatches — concurrent small requests fill one power-of-two bucket with
  real frames instead of each padding its own.
* ``async for hr in server.stream(frames)`` serves frame-at-a-time live
  video: each frame is submitted (a small lookahead keeps the coalescer
  fed) and HR frames are yielded in order; concurrent streams share
  dispatches.
* ``max_inflight_frames`` bounds the queue (pending + dispatched frames);
  at the bound, ``admission="block"`` drains the queue to make space,
  ``admission="reject"`` raises
  :class:`~repro.engine.scheduler.QueueFullError`, and
  ``admission="shed"`` evicts the lowest-priority, latest-deadline queued
  work (never the newcomer) — victims fail with
  :class:`~repro.engine.scheduler.RequestShedError`.
* ``submit(frames, deadline=..., timeout=...)`` attaches a per-request
  deadline: a request still fully queued when it passes is cancelled with
  :class:`~repro.engine.scheduler.DeadlineExceededError` before it ever
  compiles or dispatches — its coalesced neighbors are untouched.
* :class:`DegradePolicy` is the overload pressure valve: it watches a
  rolling p99 of end-to-end request latency (the EMA mean/var core shared
  with ``runtime.resilience.StragglerDetector``) and on sustained SLO
  breach steps down a documented ladder — bf16 dispatch dtype, halved
  ``stream()`` lookahead, halved buckets — stepping back up on recovery;
  every transition is logged in ``stats()``.
* A ``runtime.resilience.FailureInjector`` passed as ``injector=``
  intercepts every launch (fail the k-th dispatch, delay a replica,
  poison a model): injected faults flow through the normal
  dispatch-failure isolation, so only the affected requests fail.

Execution is the PIPELINED drain loop that previously lived inside
``SRSession``: each dispatch is assembled (host frames through the
session's one reused staging buffer, device frames through a fused pad /
concatenate), launched asynchronously, and completed in order, with up to
``session.pipeline_depth`` dispatches in flight per session.  Latency,
span and peak-inflight numbers are recorded on the owning session —
``session.stats()`` means the same thing whether a batch arrived through
``upscale``, ``submit`` or a stream.  Dispatch formation runs under one
server lock, but device waits release it: ``SRFuture.result()`` from any
thread drives the drain, and while one thread blocks on the device other
threads' submits are admitted — and coalesce into the next dispatch.

``SRSession.upscale`` is now a thin synchronous shim over
``session.submit(frames).result()`` — routed through the server hosting
the session (one scheduler and one lock govern all traffic into it), or
through an embedded single-model server when none does — so the blocking
API and the future API are the same code path.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.scheduler import (
    DeadlineExceededError,
    Dispatch,
    MicroBatchScheduler,
    QueueFullError,
    RequestShedError,
    SchedRequest,
)
from repro.engine.session import SRSession
from repro.runtime.resilience import EMAMeanVar

__all__ = [
    "SRServer",
    "SRFuture",
    "QueueFullError",
    "DeadlineExceededError",
    "RequestShedError",
    "RequestCancelledError",
    "DegradePolicy",
    "DEGRADE_LADDER",
]

ADMISSION_POLICIES = ("block", "reject", "shed")


class RequestCancelledError(RuntimeError):
    """The submitter cancelled the request (e.g. an abandoned stream)."""

# The degradation ladder, mildest first; level k applies steps 1..k.
DEGRADE_LADDER = ("full", "bf16", "half_lookahead", "half_buckets")


class DegradePolicy:
    """Degrade-under-pressure controller for :class:`SRServer`.

    Watches a rolling p99 estimate of END-TO-END request latency
    (admission to future resolution, milliseconds): an
    :class:`~repro.runtime.resilience.EMAMeanVar` — the same moving
    mean/variance core ``StragglerDetector`` uses for training-step
    latencies — approximates p99 as ``mean + 2.326 sigma``.  O(1) per
    observation, no reservoir, and monotone in both load and jitter,
    which is what a pressure signal needs.

    The ladder (:data:`DEGRADE_LADDER`), mildest first; level k applies
    every step up to k:

    1. ``bf16`` — fp32 requests dispatch in bf16 (half the slab traffic
       per frame; the paper's own on-chip compute precision).
    2. ``half_lookahead`` — ``stream()`` halves its lookahead window
       (fewer speculative frames queued per live stream).
    3. ``half_buckets`` — freshly derived dispatch buckets are halved
       (lower per-dispatch latency at some throughput cost; carry-pinned
       buckets are never resized mid-clip).

    Hysteresis: stepping DOWN takes ``breach_steps`` consecutive
    observations with the p99 estimate over ``slo_p99_ms``; stepping UP
    takes ``recover_steps`` consecutive observations at or under
    ``recover_fraction * slo_p99_ms``.  One outlier cannot flap the
    ladder.  Every transition is recorded (``transitions``, surfaced by
    ``SRServer.stats()``).

    Thread-safety: the server calls :meth:`observe` and reads the level
    under its own lock; the policy object itself keeps no lock.
    """

    #: z for the normal-approximation p99 (Phi(2.326) ~ 0.99)
    P99_Z = 2.326

    def __init__(self, slo_p99_ms: float, *, alpha: float = 0.1,
                 breach_steps: int = 3, recover_steps: int = 8,
                 recover_fraction: float = 0.5):
        if slo_p99_ms <= 0:
            raise ValueError(f"slo_p99_ms={slo_p99_ms} must be > 0")
        if breach_steps < 1 or recover_steps < 1:
            raise ValueError("breach_steps and recover_steps must be >= 1")
        if not 0 < recover_fraction <= 1:
            raise ValueError(
                f"recover_fraction={recover_fraction} must be in (0, 1]"
            )
        self.slo_p99_ms = float(slo_p99_ms)
        self.breach_steps = int(breach_steps)
        self.recover_steps = int(recover_steps)
        self.recover_fraction = float(recover_fraction)
        self._ema = EMAMeanVar(alpha)
        self.level = 0
        self.observations = 0
        self.degraded_requests = 0  # requests admitted at level > 0
        self.transitions: list = []
        self._breach = 0
        self._recover = 0

    @property
    def p99_ms(self) -> float:
        """The rolling p99 estimate (0.0 until the first observation)."""
        return self._ema.upper(self.P99_Z)

    def observe(self, latency_ms: float) -> Optional[dict]:
        """Fold one completed request's end-to-end latency; returns the
        transition record if this observation moved the ladder."""
        self.observations += 1
        self._ema.fold(latency_ms)
        p99 = self.p99_ms
        if p99 > self.slo_p99_ms:
            self._breach += 1
            self._recover = 0
            if (self._breach >= self.breach_steps
                    and self.level < len(DEGRADE_LADDER) - 1):
                return self._transition(self.level + 1, p99, "slo_breach")
        elif p99 <= self.recover_fraction * self.slo_p99_ms:
            self._recover += 1
            self._breach = 0
            if self._recover >= self.recover_steps and self.level > 0:
                return self._transition(self.level - 1, p99, "recovered")
        else:
            # between the recovery band and the SLO: steady state, reset
            # both streaks — neither direction is earning a transition
            self._breach = 0
            self._recover = 0
        return None

    def _transition(self, to: int, p99: float, reason: str) -> dict:
        t = {
            "from": self.level,
            "to": to,
            "from_step": DEGRADE_LADDER[self.level],
            "to_step": DEGRADE_LADDER[to],
            "p99_ms": round(p99, 3),
            "slo_p99_ms": self.slo_p99_ms,
            "reason": reason,
            "observation": self.observations,
        }
        self.level = to
        self._breach = 0
        self._recover = 0
        self.transitions.append(t)
        return t

    # --- the knobs the server consults, one per ladder step -----------
    def serve_dtype(self, dtype: np.dtype) -> np.dtype:
        """Dispatch dtype at the current level (level >= 1: fp32 -> bf16)."""
        if self.level >= 1 and np.dtype(dtype) == np.float32:
            return np.dtype(jnp.bfloat16)
        return np.dtype(dtype)

    def lookahead(self, base: int) -> int:
        """Stream lookahead at the current level (level >= 2: halved)."""
        return max(1, base // 2) if self.level >= 2 else base

    def bucket_cap(self, bucket: int) -> int:
        """Dispatch bucket at the current level (level >= 3: halved)."""
        return max(1, bucket // 2) if self.level >= 3 else bucket

    def stats(self) -> dict:
        return {
            "level": self.level,
            "step": DEGRADE_LADDER[self.level],
            "ladder": list(DEGRADE_LADDER),
            "slo_p99_ms": self.slo_p99_ms,
            "p99_ms": round(self.p99_ms, 3),
            "observations": self.observations,
            "degraded_requests": self.degraded_requests,
            "transitions": list(self.transitions),
        }


class SRFuture:
    """The result handle ``SRServer.submit`` returns.

    ``result()`` drives the server's drain loop until this request's
    frames are served (so a single-threaded caller needs no background
    worker), then returns the HR array in the request's original rank —
    or re-raises the error that failed the dispatch.  Thread-safe: any
    number of threads may wait; whoever gets the server lock drains,
    the rest block until notified.
    """

    def __init__(self, server: "SRServer"):
        self._server = server
        self._cond = threading.Condition()
        self._done = False
        self._result = None
        self._exc: Optional[BaseException] = None
        self._callbacks = []
        # backref to the admitted SchedRequest — what SRServer.cancel
        # uses to drop the queued remainder of an abandoned request
        self._request = None

    def done(self) -> bool:
        return self._done

    def _wait_done(self, timeout: Optional[float]) -> None:
        """Drive the drain, then wait for completion — both bounded by one
        monotonic deadline.

        ``timeout`` is WALL-CLOCK from this call: a drain this call
        performs itself checks the deadline between steps (so a caller
        driving the drain still gets a timely ``TimeoutError``), and the
        wait loops on the condition until done or due — a single
        ``cond.wait(timeout)`` could return early on a spurious wakeup
        and then either under-wait or over-wait.
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        if not self._done:
            self._server._drain_until(self, deadline=deadline)
        with self._cond:
            while not self._done:
                if deadline is None:
                    self._cond.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("request not complete within timeout")
                self._cond.wait(remaining)

    def result(self, timeout: Optional[float] = None):
        """The request's HR output (blocking; drives the server's drain),
        or re-raises the error that failed the request."""
        self._wait_done(timeout)
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        """The error that failed this request, or ``None`` (blocking; a
        stored failure is RETURNED — even a ``TimeoutError`` raised by the
        dispatch — while an unfinished wait raises ``TimeoutError``)."""
        self._wait_done(timeout)
        return self._exc

    def add_done_callback(self, fn) -> None:
        """Run ``fn(self)`` when the future resolves (immediately if it
        already has).  Callbacks run on the draining thread, OUTSIDE the
        server lock — a callback may submit follow-up work or wait on
        other futures without deadlocking."""
        with self._cond:
            if not self._done:
                self._callbacks.append(fn)
                return
        fn(self)

    def _finish(self, result=None, exc: Optional[BaseException] = None) -> None:
        """Set the outcome and wake waiters.  Callbacks are NOT run here —
        this executes under the server lock; the server runs
        :meth:`_run_callbacks` after releasing it."""
        with self._cond:
            self._result = result
            self._exc = exc
            self._done = True
            self._cond.notify_all()

    def _run_callbacks(self) -> None:
        with self._cond:
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)


class _Inflight:
    """One launched dispatch: the async HR handle, its launch span's
    bounds (``t0`` the executor call, ``t1`` its return) and whether it
    staged through the session's shared host buffer."""

    __slots__ = ("dispatch", "hr", "t0", "t1", "used_staging")

    def __init__(self, dispatch: Dispatch, hr, t0: float, t1: float,
                 used_staging: bool):
        self.dispatch = dispatch
        self.hr = hr
        self.t0 = t0
        self.t1 = t1
        self.used_staging = used_staging


class SRServer:
    """One serving endpoint hosting named sessions behind a micro-batcher.

    ``sessions`` maps model names to :class:`SRSession`\\ s (a bare session
    is accepted and hosted under its model name).  ``default_model`` is the
    target when ``submit`` is called without ``model=`` (defaults to the
    first session).  ``max_inflight_frames`` bounds pending + dispatched
    frames; ``admission`` picks the full-queue behavior (``"block"`` drains
    to make space, ``"reject"`` raises :class:`QueueFullError`, ``"shed"``
    evicts the lowest-priority latest-deadline queued work to make room —
    or rejects the newcomer when it is itself the least urgent).
    ``degrade`` installs a :class:`DegradePolicy`; ``injector`` a
    :class:`~repro.runtime.resilience.FailureInjector` consulted before
    every launch (tests/load harness only — injected faults fail exactly
    the dispatch they target).
    """

    def __init__(
        self,
        sessions: Union[SRSession, Mapping[str, SRSession]],
        *,
        default_model: Optional[str] = None,
        max_inflight_frames: Optional[int] = None,
        admission: str = "block",
        degrade: Optional[DegradePolicy] = None,
        injector=None,
    ):
        if isinstance(sessions, SRSession):
            sessions = {sessions.model or "default": sessions}
        sessions = dict(sessions)
        if not sessions:
            raise ValueError("SRServer needs at least one session")
        for name, s in sessions.items():
            if not isinstance(name, str):
                raise ValueError(f"model name {name!r} must be a string")
            if not isinstance(s, SRSession):
                raise ValueError(
                    f"model {name!r} must map to an SRSession, got {type(s).__name__}"
                )
        if max_inflight_frames is not None and max_inflight_frames < 1:
            raise ValueError(
                f"max_inflight_frames={max_inflight_frames} must be >= 1 "
                "(or None for an unbounded queue)"
            )
        if admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"admission {admission!r} not in {ADMISSION_POLICIES}"
            )
        if admission == "shed" and max_inflight_frames is None:
            raise ValueError(
                'admission="shed" needs a max_inflight_frames bound — '
                "an unbounded queue never sheds"
            )
        if degrade is not None and not isinstance(degrade, DegradePolicy):
            raise ValueError(
                f"degrade must be a DegradePolicy, got {type(degrade).__name__}"
            )
        if injector is not None and not hasattr(injector, "on_dispatch"):
            raise ValueError(
                "injector must expose on_dispatch(model=, replica=) — "
                "see repro.runtime.resilience.FailureInjector"
            )
        if default_model is None:
            default_model = next(iter(sessions))
        if default_model not in sessions:
            raise ValueError(
                f"default_model {default_model!r} not among hosted models "
                f"{sorted(sessions)}"
            )
        self._sessions = sessions
        self._default = default_model
        self.max_inflight_frames = max_inflight_frames
        self.admission = admission
        self._degrade = degrade
        self._injector = injector
        # hosted sessions route their own submit()/upscale() through THIS
        # server, so one lock + one scheduler govern all traffic into the
        # session; a SECOND front door over the same mutable session state
        # (staging buffer, caches, stats) would race it, so hosting an
        # already-served session is an error rather than a silent hazard
        for s in sessions.values():
            if s._server is None:
                s._server = self
            elif s._server is not self:
                raise ValueError(
                    "session is already served by another SRServer (its "
                    "upscale()/submit() traffic routes there); host each "
                    "session in exactly one server — construct the hosting "
                    "server before serving through the session directly"
                )
        self._sched = MicroBatchScheduler()
        # one lock guards scheduler + inflight state; the condition lets a
        # thread RELEASE it while blocking on the device (completions in
        # progress are counted in _completing and waited on via the cv),
        # so concurrent submits are admitted — and coalesce — while a
        # drain is waiting on compute
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._completing = 0  # dispatches being block_until_ready'd off-lock
        self._inflight: Deque[_Inflight] = deque()
        self._inflight_frames = 0  # dispatched, not yet complete (real)
        self._session_inflight: Dict[int, int] = {}
        self._window_start: Dict[int, float] = {}
        # per-session count of in-flight dispatches staged through the
        # session's SHARED host buffer: while one is outstanding, the next
        # host dispatch stages through a fresh buffer instead — the H2D
        # copy of dispatch t may still be reading the buffer when t+1
        # assembles (a hazard only on overlapped host dispatches)
        self._staging_busy: Dict[int, int] = {}
        # futures finished inside a locked region, whose done-callbacks
        # still need to run once the lock is released
        self._just_finished: list = []
        self._closed = False

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------
    @classmethod
    def open(
        cls,
        *models: str,
        default_model: Optional[str] = None,
        max_inflight_frames: Optional[int] = None,
        admission: str = "block",
        degrade: Optional[DegradePolicy] = None,
        injector=None,
        seed: int = 0,
        # the benchmark harness still passes it; it goes once that stops
        tuning_db: Optional[str] = None,
        **session_kwargs,
    ) -> "SRServer":
        """Open a server hosting registered SR models by name.

        Each name resolves through ``repro.models.registry``
        (``list_sr_models()`` enumerates them); ``session_kwargs``
        (backend, precision, pipeline_depth, max_bucket, ...) apply to
        every hosted session.  With no names, hosts the paper's
        ``abpn_x3``.
        """
        names = models or ("abpn_x3",)
        sessions = {
            name: SRSession.open(name, seed=seed, **session_kwargs)
            for name in names
        }
        return cls(
            sessions,
            default_model=default_model,
            max_inflight_frames=max_inflight_frames,
            admission=admission,
            degrade=degrade,
            injector=injector,
        )

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------
    @property
    def models(self) -> Tuple[str, ...]:
        return tuple(self._sessions)

    def session(self, model: Optional[str] = None) -> SRSession:
        """The hosted session serving ``model`` (default model if None)."""
        return self._sessions[self._resolve_model(model)]

    def scheduler_stats(self) -> dict:
        """The micro-batcher's coalescing/queue counters plus the server's
        in-flight state (see ``MicroBatchScheduler.stats``)."""
        with self._lock:
            stats = self._sched.stats()
            stats["inflight_dispatches"] = len(self._inflight)
            stats["inflight_frames"] = self._inflight_frames
            stats["recent_dispatches"] = list(self._sched.recent_dispatches)
        return stats

    def stats(self) -> dict:
        """Scheduler counters, each hosted session's serving stats, and —
        when a :class:`DegradePolicy` is installed — its level, rolling
        p99 estimate and full transition log."""
        out = {
            "scheduler": self.scheduler_stats(),
            "models": {
                name: dict(s.stats()) for name, s in self._sessions.items()
            },
        }
        if self._degrade is not None:
            with self._lock:
                out["degrade"] = self._degrade.stats()
        return out

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def _resolve_model(self, model: Optional[str]) -> str:
        name = self._default if model is None else model
        if name not in self._sessions:
            raise ValueError(
                f"unknown model {name!r}; this server hosts {sorted(self._sessions)}"
            )
        return name

    def _name_for(self, session: SRSession) -> str:
        """The hosted name of a session (identity lookup)."""
        for name, s in self._sessions.items():
            if s is session:
                return name
        raise ValueError("session is not hosted by this server")

    def submit_for(self, session: SRSession, frames, *, priority: int = 0,
                   deadline: Optional[float] = None,
                   timeout: Optional[float] = None) -> SRFuture:
        """Submit addressed by hosted session identity rather than name —
        what ``SRSession.submit`` calls on its hosting server."""
        return self.submit(frames, model=self._name_for(session),
                           priority=priority, deadline=deadline,
                           timeout=timeout)

    def submit(self, frames, *, model: Optional[str] = None,
               priority: int = 0, deadline: Optional[float] = None,
               timeout: Optional[float] = None) -> SRFuture:
        """Queue a request; returns its :class:`SRFuture` immediately.

        ``frames`` is any rank ``upscale`` accepts (``(H, W, C)``,
        ``(T, H, W, C)``, ``(B, T, H, W, C)``); validation (array-ness,
        numeric dtype, rank, channel count) happens HERE, synchronously,
        so malformed input fails with a clear ``ValueError`` instead of
        surfacing from plan derivation or compilation.  Higher
        ``priority`` keys dispatch first.  The actual dispatch runs when
        the drain loop next turns over (``result()``/``flush()``/a
        concurrent waiter), coalescing whatever compatible requests are
        queued by then.

        ``deadline`` (absolute ``time.monotonic()`` seconds) or
        ``timeout`` (seconds from now; the two are exclusive) bounds how
        long the request may sit QUEUED: when it passes before the first
        frame dispatches, the future fails with
        :class:`DeadlineExceededError` — checked at every admission and
        drain turn, so an expired request never compiles or dispatches.
        Once frames are in flight the request runs to completion (a torn
        half-clip helps nobody); the deadline bounds queueing, not
        compute.
        """
        if self._closed:
            raise RuntimeError("server is closed")
        if deadline is not None and timeout is not None:
            raise ValueError("pass deadline= or timeout=, not both")
        if timeout is not None:
            deadline = time.monotonic() + float(timeout)
        name = self._resolve_model(model)
        session = self._sessions[name]
        with session.spans.span("submit"):
            return self._submit(session, name, frames, priority, deadline)

    def _submit(self, session: SRSession, name: str, frames, priority: int,
                deadline: Optional[float]) -> SRFuture:
        """``submit``'s body: validation, plan, admission (the ``sr.submit``
        span)."""
        flat, ndim, lead = session.flatten_request(frames)
        degraded = False
        if self._degrade is not None:
            # apply the ladder's dispatch dtype BEFORE key derivation, so
            # a degraded request coalesces with (and compiles as) bf16
            # traffic — the downcast happens here, on the host copy
            wanted = self._degrade.serve_dtype(flat.dtype)
            if wanted != flat.dtype:
                flat = flat.astype(wanted)
                degraded = True
        shape = tuple(int(x) for x in flat.shape[1:])
        n = int(flat.shape[0])
        fut = SRFuture(self)
        if deadline is not None and time.monotonic() >= deadline:
            # dead on arrival: fail before plan derivation, let alone
            # compilation — the caller's clock budget is already spent
            with self._lock:
                self._sched.expired += 1
            fut._finish(exc=DeadlineExceededError(
                "deadline exceeded on submit: the request's budget "
                "elapsed before admission"
            ))
            fut._run_callbacks()
            return fut
        plan = session.plan_for(shape)
        dtype = session.serving_dtype(flat.dtype)
        if n == 0:
            out = jnp.zeros((0, *plan.hr_shape), session.output_dtype(plan, dtype))
            if ndim == 5:
                out = out.reshape(*lead, *plan.hr_shape)
            with self._lock:
                self._sched.note_empty_request()
            fut._finish(result=out)
            return fut
        req = SchedRequest(
            seq=0,  # assigned under the lock below
            key=(name, plan, dtype.name),
            session=session,
            plan=plan,
            flat=flat,
            n=n,
            priority=int(priority),
            future=fut,
            ndim=ndim,
            lead=lead,
            deadline=deadline,
        )
        fut._request = req
        self._admit(req)
        if degraded:
            with self._lock:
                self._degrade.degraded_requests += 1
        return fut

    def submit_bands(self, slabs, bands, *, plan, model: Optional[str] = None,
                     priority: int = 0) -> SRFuture:
        """Queue a partial-band request (the temporal delta path).

        ``slabs`` is a host ``(k, rows, W, C)`` array of per-band input
        slabs in the plan's band-input geometry (``rows = R + 2L`` under
        ``halo``, the ``core.fusion.halo_slabs`` layout; ``R`` rows
        otherwise) and ``bands`` the matching strictly-increasing band
        indices.  The future resolves to the ``(k, R*s, W*s, C)`` HR
        band stack.  Band requests ride the same scheduler as frames
        under a ``"bands"``-suffixed coalescing key (queue units are
        BANDS, so backpressure/expiry/shedding apply unchanged, but a
        band slab never shares a dispatch with a frame).  The degrade
        policy's dtype ladder is deliberately NOT applied: delta
        streams' contract is bit-exactness with full re-upscale, and a
        mid-clip downcast would poison the output cache.
        """
        if self._closed:
            raise RuntimeError("server is closed")
        from repro.engine.temporal.band_diff import band_input_rows

        name = self._resolve_model(model)
        session = self._sessions[name]
        bands = tuple(int(b) for b in bands)
        if not bands:
            raise ValueError("submit_bands needs at least one band")
        if any(b2 <= b1 for b1, b2 in zip(bands, bands[1:])):
            raise ValueError(f"bands must be strictly increasing: {bands}")
        if bands[0] < 0 or bands[-1] >= plan.num_bands:
            raise ValueError(
                f"bands {bands} out of range [0, {plan.num_bands})"
            )
        flat = np.asarray(slabs)
        dtype = session.serving_dtype(flat.dtype)
        flat = np.ascontiguousarray(flat.astype(dtype, copy=False))
        rows = band_input_rows(
            plan.band_rows, plan.num_layers, plan.vertical_policy
        )
        want = (len(bands), rows, plan.width, plan.in_channels)
        if flat.shape != want:
            raise ValueError(
                f"band slabs shape {flat.shape} != expected {want} for "
                f"{len(bands)} band(s) of plan {plan.height}x{plan.width} "
                f"({plan.vertical_policy})"
            )
        fut = SRFuture(self)
        req = SchedRequest(
            seq=0,  # assigned under the lock in _admit
            key=(name, plan, dtype.name, "bands"),
            session=session,
            plan=plan,
            flat=flat,
            n=len(bands),
            priority=int(priority),
            future=fut,
            ndim=4,  # identity assembly: the future gets the raw stack
            lead=None,
            bands=bands,
        )
        fut._request = req
        self._admit(req)
        return fut

    def cancel(self, fut: SRFuture) -> bool:
        """Best-effort cancel of a submitted request (the stream-abandon
        path).  The queued remainder is dropped — releasing any
        carry-pinned bucket — and the future fails with
        :class:`RequestCancelledError`; frames already inside an
        in-flight dispatch complete on-device and are discarded.
        Returns False if the future is already resolved (its result
        stands) or was never admitted."""
        req = fut._request
        if req is None:
            return False
        with self._lock:
            if fut.done():
                return False
            req.failed = True
            self._sched.drop(req)
            fut._finish(exc=RequestCancelledError(
                "request cancelled by its submitter"
            ))
            self._just_finished.append(fut)
            finished = self._take_finished()
        self._run_finished(finished)
        return True

    def _expire_locked(self, now: float) -> None:
        """Cancel queued past-deadline requests (call holding the lock):
        each fails with :class:`DeadlineExceededError` before compiling or
        dispatching; callbacks run via ``_just_finished`` off-lock."""
        for r in self._sched.expire_due(now):
            r.failed = True
            r.future._finish(exc=DeadlineExceededError(
                f"deadline exceeded: {r.n} frames still queued when the "
                "request's deadline passed (never dispatched)"
            ))
            self._just_finished.append(r.future)

    def _admit(self, req: SchedRequest) -> None:
        bound = self.max_inflight_frames
        if bound is not None and req.n > bound:
            raise ValueError(
                f"request of {req.n} frames can never fit "
                f"max_inflight_frames={bound}"
            )
        while True:
            err: Optional[BaseException] = None
            admitted = False
            done = False
            with self._lock:
                # expire due work first: a stale queue must not block or
                # shed live traffic a deadline already freed
                self._expire_locked(time.monotonic())
                queued = self._sched.pending_frames + self._inflight_frames
                if (req.deadline is not None
                        and time.monotonic() >= req.deadline):
                    # the budget elapsed while blocked at admission — the
                    # request expires unqueued, same contract as expiry
                    self._sched.expired += 1
                    req.failed = True
                    req.future._finish(exc=DeadlineExceededError(
                        "deadline exceeded during admission: the queue "
                        "stayed full past the request's budget"
                    ))
                    self._just_finished.append(req.future)
                    done = True
                elif bound is None or queued + req.n <= bound:
                    req.seq = self._sched.next_seq()
                    req.admitted_at = time.monotonic()
                    self._sched.add(req)
                    admitted = True
                elif self.admission == "reject":
                    self._sched.note_rejected()
                    err = QueueFullError(
                        f"queue full: {queued} frames in flight + {req.n} "
                        f"requested > max_inflight_frames={bound}"
                    )
                elif self.admission == "shed":
                    victims = self._sched.shed_victims(
                        queued + req.n - bound,
                        priority=req.priority, deadline=req.deadline,
                    )
                    if victims is None:
                        # nothing queued ranks below the newcomer — IT is
                        # the least-urgent work, so it takes the rejection
                        self._sched.note_rejected()
                        err = QueueFullError(
                            f"queue full: {queued} frames in flight + "
                            f"{req.n} requested > max_inflight_frames="
                            f"{bound}, and no queued work ranks below the "
                            "new request"
                        )
                    else:
                        for v in victims:
                            v.failed = True
                            v.future._finish(exc=RequestShedError(
                                f"shed: {v.n} queued frames (priority "
                                f"{v.priority}) evicted for a priority-"
                                f"{req.priority} request at a full queue"
                            ))
                            self._just_finished.append(v.future)
                        req.seq = self._sched.next_seq()
                        req.admitted_at = time.monotonic()
                        self._sched.add(req)
                        admitted = True
                else:
                    # a full queue implies drainable work (checked under
                    # the SAME lock as the fullness read — another thread
                    # may have drained it by the time our step runs,
                    # which is fine)
                    if not (self._sched.has_pending() or self._inflight
                            or self._completing):
                        raise RuntimeError(
                            "queue full but no work to drain — "
                            "inconsistent scheduler state"
                        )
                finished = self._take_finished()
            self._run_finished(finished)
            if err is not None:
                raise err
            if admitted or done:
                return
            # block policy: make space by draining the queue (outside the
            # lock — _step synchronizes itself), then re-check admission
            self._step()

    # ------------------------------------------------------------------
    # The drain loop
    # ------------------------------------------------------------------
    def flush(self) -> None:
        """Drain everything: dispatch all pending frames and complete all
        in-flight dispatches (their futures resolve)."""
        while self._step():
            pass

    def _drain_until(self, fut: SRFuture,
                     deadline: Optional[float] = None) -> None:
        """Drive the drain until ``fut`` resolves — or until ``deadline``
        (absolute monotonic) passes, in which case this returns with the
        request still queued/in flight and the caller's wait raises."""
        while not fut.done():
            if deadline is not None and time.monotonic() >= deadline:
                return
            if not self._step():
                if fut.done():
                    # a concurrent thread finalized the future between our
                    # done() check and _step() taking the lock — fine
                    return
                raise RuntimeError(
                    "future is not done but the server has no pending "
                    "work — was it issued by this server?"
                )

    def _session_ready(self, session: SRSession) -> bool:
        return self._session_inflight.get(id(session), 0) < session.pipeline_depth

    def _step(self) -> bool:
        """One drain turn: launch the next dispatch if a session has
        pipeline-depth slack, else complete the oldest in-flight one.
        Returns False when there is nothing left to do.

        Synchronizes itself: launches (assembly + async dispatch + any
        cache-miss compile) run under the lock; the device wait of a
        completion runs with the lock RELEASED, counted in
        ``_completing`` so other threads know progress is in flight —
        they wait on the condition instead of reporting starvation, and
        their submits are admitted (and coalesce) meanwhile.  Futures
        finished inside a locked region run their done-callbacks here,
        after the lock is released.  (Known trade vs the old in-session
        loop: a depth-1 session no longer stages chunk t+1 during chunk
        t's device wait — the next dispatch assembles only after the
        completion frees depth slack.)
        """
        inf = None
        progress = True
        with self._cv:
            # cancel past-deadline queued work BEFORE forming a dispatch:
            # an expired request must never reach compilation, and its
            # frames must not inflate the bucket choice
            self._expire_locked(time.monotonic())
            bucket_fn = (self._degrade.bucket_cap
                         if self._degrade is not None else None)
            d = self._sched.next_dispatch(self._session_ready, bucket_fn)
            if d is not None:
                self._launch(d)  # a launch FAILURE finishes futures
            elif self._inflight:
                inf = self._inflight.popleft()
                self._completing += 1
            elif self._completing:
                # another thread is waiting on a completion — progress is
                # theirs to make; sleep until its finalize wakes us
                self._cv.wait()
            else:
                # no dispatch, nothing in flight: this turn made progress
                # only if expiry just finished futures
                progress = bool(self._just_finished)
            finished = self._take_finished()
        if inf is None:
            self._run_finished(finished)
            return progress
        self._run_finished(finished)
        spans = inf.dispatch.session.spans
        error: Optional[BaseException] = None
        try:
            with spans.span("device_wait") as wait:
                jax.block_until_ready(inf.hr)  # off-lock device wait
            spans.record("device_ready", (wait.t1 - inf.t1) * 1e3)
        except BaseException as e:  # deferred device-side failure
            error = e
        with spans.span("complete") as complete:
            with self._cv:
                try:
                    self._finalize_complete(inf, error, complete.t0)
                finally:
                    self._completing -= 1
                    self._cv.notify_all()
                finished = self._take_finished()
            self._run_finished(finished)
        return True

    def _take_finished(self) -> list:
        finished, self._just_finished = self._just_finished, []
        return finished

    @staticmethod
    def _run_finished(finished: list) -> None:
        for fut in finished:
            fut._run_callbacks()

    def _launch(self, d: Dispatch) -> None:
        session: SRSession = d.session
        spans = session.spans
        now = time.monotonic()
        for t in d.tickets:
            if t.start == 0:  # the dispatch carries the request's first frame
                spans.record("queue_wait", (now - t.request.admitted_at) * 1e3)
        try:
            # executor resolution may compile — on a dummy, before the
            # timed dispatch starts, exactly like the pre-server path
            if d.band_subset is not None:
                entry, _ = session.band_executor_for(
                    d.plan, d.bucket, np.dtype(d.key[2])
                )
            else:
                entry, _ = session.executor_for(
                    d.plan, d.bucket, np.dtype(d.key[2])
                )
            if self._injector is not None:
                # fault-injection point (tests/load harness): a raise here
                # flows through _fail_dispatch below — exactly this
                # dispatch's requests fail, everything else keeps serving
                self._injector.on_dispatch(
                    model=d.key[0], replica=getattr(entry, "replica", None)
                )
            if d.band_subset is not None:
                with spans.span("assemble"):
                    slab, bounds = self._assemble_bands(d)
                used_staging = False
                with spans.span("launch") as launch:
                    hr = entry.fn(slab, bounds)  # async dispatch
            else:
                with spans.span("assemble"):
                    slab, used_staging = self._assemble(d, entry)
                with spans.span("launch") as launch:
                    hr = entry.fn(slab)  # async dispatch: returns immediately
        except BaseException as e:
            self._fail_dispatch(d, e)
            return
        # mesh serving: credit the routing decision — the scheduler's
        # replica counters and the router's live load both key off it
        d.replica = getattr(entry, "replica", None)
        if d.replica is not None:
            self._sched.note_routed(d.replica)
            if session._router is not None:
                session._router.note_launch(d.replica, d.real)
        sid = id(session)
        count = self._session_inflight.get(sid, 0)
        if count == 0:
            self._window_start[sid] = launch.t0
        self._session_inflight[sid] = count + 1
        session._peak_inflight = max(session._peak_inflight, count + 1)
        self._inflight_frames += d.real
        if used_staging:
            self._staging_busy[sid] = self._staging_busy.get(sid, 0) + 1
        self._inflight.append(
            _Inflight(d, hr, launch.t0, launch.t1, used_staging))

    def _assemble(self, d: Dispatch, entry):
        """Build the bucket-sized device slab from the dispatch's tickets
        for the executor ``entry``; returns ``(slab, used_shared_staging)``.

        All-host tickets go through the session's reused staging buffer
        (zero fresh bucket allocations per ragged dispatch) and one
        host-to-device copy (:meth:`_put`) — unless an in-flight dispatch
        is still using that buffer (overlapped host dispatches), in which
        case a fresh buffer keeps the earlier H2D copy safe.  Device
        tickets use a single fused ``jnp.pad`` or ``jnp.concatenate``.
        Under donation the returned slab is always server-owned: a
        full-cover slice that would hand back a caller's own array object
        is copied first.
        """
        session: SRSession = d.session
        tickets = d.tickets
        real = d.real
        if all(isinstance(t.request.flat, np.ndarray) for t in tickets):
            first = tickets[0]
            if len(tickets) == 1 and real == d.bucket:
                src = first.request.flat
                return self._put(entry, session.spans,
                                 src[first.start:first.start + first.n]), False
            frame_shape = first.request.flat.shape[1:]
            dtype = first.request.flat.dtype
            shared = not self._staging_busy.get(id(session), 0)
            if shared:
                buf = session._staging_for(d.bucket, frame_shape, dtype)
            else:
                buf = np.zeros((d.bucket, *frame_shape), dtype)
            for t in tickets:
                buf[t.slot:t.slot + t.n] = t.request.flat[t.start:t.start + t.n]
            buf[real:] = 0
            return self._put(entry, session.spans, buf), shared
        pieces = [t.request.flat[t.start:t.start + t.n] for t in tickets]
        if len(pieces) == 1:
            chunk = pieces[0]
            if isinstance(chunk, np.ndarray):
                chunk = jnp.asarray(chunk)
            if real < d.bucket:
                pad = [(0, d.bucket - real)] + [(0, 0)] * (chunk.ndim - 1)
                return jnp.pad(chunk, pad), False
            if entry.donates and chunk is tickets[0].request.flat:
                # a full-cover slice is the SAME array object in jax;
                # donating it would consume the caller's buffer
                chunk = jnp.array(chunk)
            return chunk, False
        if real < d.bucket:
            pieces.append(jnp.zeros((d.bucket - real, *pieces[0].shape[1:]),
                                    pieces[0].dtype))
        return jnp.concatenate(pieces, axis=0), False

    @staticmethod
    def _put(entry, spans, host: np.ndarray) -> jax.Array:
        """One host-to-device copy of an assembled slab: onto the default
        device, or, for a band-sharded executor, straight into its input
        sharding (``sr.shard_put``), so that each chip receives its own
        rows and none passes through another chip first."""
        if entry.in_sharding is None:
            return jax.device_put(host)
        with spans.span("shard_put"):
            return jax.device_put(host, entry.in_sharding)

    def _assemble_bands(self, d: Dispatch):
        """Build a band dispatch's ``(slab, bounds)`` device pair.

        Band slabs always stage through a fresh host buffer — never the
        session's shared staging buffer, whose shape bookkeeping is
        per-frame — and the per-slot valid-row bounds are derived
        statically from the dispatched band indices (the same
        ``halo_slabs`` clip formula; ``band_diff.band_bounds`` is its
        host mirror).  Padded slots keep ``(0, 0)``: every row phantom,
        so a padding slab computes zero features and its HR rows are
        never read back.
        """
        from repro.engine.temporal.band_diff import band_bounds

        plan = d.plan
        first = d.tickets[0].request.flat
        buf = np.zeros((d.bucket, *first.shape[1:]), first.dtype)
        for t in d.tickets:
            buf[t.slot:t.slot + t.n] = t.request.flat[t.start:t.start + t.n]
        bounds = band_bounds(
            plan.height, plan.band_rows, plan.num_layers, d.band_subset,
            slots=d.bucket,
        )
        return jax.device_put(buf), jax.device_put(bounds)

    def _finalize_complete(self, inf: _Inflight,
                           error: Optional[BaseException], now: float) -> None:
        """Bookkeeping for a completed (or device-failed) dispatch — runs
        under the lock, after the off-lock ``block_until_ready``; ``now``
        (``spans.clock()`` seconds) is when completion began."""
        d, session = inf.dispatch, inf.dispatch.session
        sid = id(session)
        # release the replica's in-flight slot FIRST — device failures must
        # not leave a replica looking permanently loaded
        if d.replica is not None and session._router is not None:
            session._router.note_complete(d.replica)
        self._inflight_frames -= d.real
        self._session_inflight[sid] -= 1
        if self._session_inflight[sid] == 0:
            session._span_s += now - self._window_start.pop(sid)
        if inf.used_staging:
            self._staging_busy[sid] -= 1
        if error is not None:
            self._fail_dispatch(d, error)
            return
        session.spans.record("latency", (now - inf.t0) * 1e3)
        if d.band_subset is None:
            session._frames += d.real
        else:
            # partial-band traffic: counted in band-rows of compute, not
            # frames — the temporal stats' reuse accounting keys off this
            session._band_rows_served += d.real * d.plan.band_rows
            session._band_dispatches += 1
        for t in d.tickets:
            r = t.request
            if r.failed:
                continue
            # keyed by the ticket's offset: concurrent drains may finalize
            # a long request's dispatches out of order
            r.pieces.append((t.start, inf.hr[t.slot:t.slot + t.n]))
            r.completed += t.n
            if r.completed == r.n:
                self._finish_request(r)

    def _finish_request(self, req: SchedRequest) -> None:
        pieces = [p for _, p in sorted(req.pieces, key=lambda sp: sp[0])]
        out = pieces[0] if len(pieces) == 1 else jnp.concatenate(
            pieces, axis=0)
        req.pieces = []
        if req.ndim == 3:
            out = out[0]
        elif req.ndim == 5:
            out = out.reshape(*req.lead, *req.plan.hr_shape)
        req.future._finish(result=out)
        self._just_finished.append(req.future)
        if self._degrade is not None and req.admitted_at:
            # end-to-end latency (admission -> resolution) is the pressure
            # signal: unlike per-dispatch latency it sees queue delay,
            # which is what overload actually inflates
            self._degrade.observe((time.monotonic() - req.admitted_at) * 1e3)

    def _fail_dispatch(self, d: Dispatch, exc: BaseException) -> None:
        """A dispatch failed (build, launch or device error): fail every
        involved request's future and drop their queued remainders — other
        keys keep serving."""
        for r in d.requests:
            if r.failed:
                continue
            r.failed = True
            self._sched.drop(r)
            r.future._finish(exc=exc)
            self._just_finished.append(r.future)

    # ------------------------------------------------------------------
    # Streaming
    # ------------------------------------------------------------------
    async def stream(self, frames, *, model: Optional[str] = None,
                     priority: int = 0, lookahead: int = 4,
                     delta: bool = False,
                     cache_bytes: Optional[int] = None):
        """Serve an iterable of frames one at a time; yields HR frames in
        order (an async generator — ``async for hr in server.stream(...)``).

        ``lookahead`` frames are submitted ahead of the one being awaited,
        which keeps the micro-batcher's queue non-empty: a single stream
        coalesces its own lookahead window into full buckets, and
        concurrent streams share dispatches with each other.  Waiting
        happens off the event loop (``asyncio.to_thread``), so multiple
        streams interleave.  Under an active :class:`DegradePolicy` at
        level >= 2 the window is halved — re-read each turn, so a
        mid-stream transition takes effect on the next frame.

        ``delta=True`` serves the clip through a
        :class:`~repro.engine.temporal.DeltaSession`: each frame is
        band-diffed against the previous one, only dirty bands dispatch
        (as partial-band dispatches), and clean bands splice from the
        session's output cache — bit-exact vs full re-upscale.  Delta
        streams are sequential by construction (frame k's dirty set
        needs frame k-1's digests), so ``lookahead`` does not apply;
        ``cache_bytes`` bounds the output cache.  Abandoning either kind
        of stream (closing the generator mid-clip) cancels its pending
        requests and releases its cache pins — no carry bucket or
        refcount leaks.
        """
        import asyncio

        if delta:
            from repro.engine.temporal import DeltaSession

            ds = DeltaSession(self.session(model), server=self,
                              priority=priority, cache_bytes=cache_bytes)
            try:
                for frame in frames:
                    yield await asyncio.to_thread(ds.serve, frame)
            finally:
                ds.close()
            return

        base = max(1, int(lookahead))
        pending: Deque[SRFuture] = deque()
        it = iter(frames)
        exhausted = False
        try:
            while pending or not exhausted:
                window = (self._degrade.lookahead(base)
                          if self._degrade is not None else base)
                while not exhausted and len(pending) < window:
                    try:
                        frame = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    # submit off the loop too: with a full bounded queue
                    # and admission="block" it drains (device waits)
                    # until space
                    pending.append(await asyncio.to_thread(
                        self.submit, frame, model=model, priority=priority))
                if pending:
                    fut = pending.popleft()
                    yield await asyncio.to_thread(fut.result)
        finally:
            # abandoned mid-clip: drop the lookahead window's queued
            # frames so they don't dispatch (or pin a carry bucket) for
            # a consumer that is gone
            while pending:
                self.cancel(pending.popleft())

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def close(self) -> None:
        """Drain outstanding work, refuse further submits, and release
        the hosted sessions so a successor server may host them (their
        compile caches carry over; the load harness leans on this to
        reuse warm sessions across server configurations)."""
        self.flush()
        self._closed = True
        for s in self._sessions.values():
            if s._server is self:
                s._server = None

    def __enter__(self) -> "SRServer":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()
