"""SRSession — shape/batch/model-agnostic serving over a compile cache.

The paper's accelerator serves ONE fixed pipeline (1080p x3 at 60 fps);
production traffic is heterogeneous: mixed resolutions, clip lengths,
batch sizes and dtypes.  ``SRSession`` is the serving front door that
absorbs that heterogeneity:

* ``SRSession.open("abpn_x3", backend=..., precision=...)`` resolves the
  model's config + weights through ``repro.models.registry``.
* ``session.upscale(frames)`` accepts ``(H, W, C)``, ``(T, H, W, C)`` or
  ``(B, T, H, W, C)`` input.  Per new resolution it derives the
  :class:`~repro.engine.plan.SRPlan` (including a legal ``band_rows`` for
  the incoming height — ``SRPlan.from_request``), buckets the flattened
  batch up to a power of two, and compiles one executor per
  ``(plan, bucket, dtype)`` on demand.
* Compiled executors live in an LRU :class:`PlanCache`; hit/miss/evict
  counters and per-entry compile times are exposed via
  :meth:`SRSession.cache_stats`.

Serving is PIPELINED — the software analogue of the paper's ping-pong
line buffers:

* Weights are prepared (quantised / cast / kernel-packed) ONCE per session
  into a device-resident :class:`~repro.engine.executor.PreparedStack`
  (refcounted across cache entries, released when the last entry using it
  is evicted), so no per-batch jitted call re-runs weight prep.
* Multi-bucket requests dispatch up to ``pipeline_depth`` chunks
  asynchronously (depth 2 by default — double buffering): while the device
  computes chunk *t*, chunk *t+1* is staged (``jax.device_put`` for host
  frames, one reused tail-padding buffer) and enqueued; blocking happens
  only when the pipeline is full and at the tail.
* ``donate_frames`` compiles executors with the frame batch donated, so
  XLA can recycle the bucket-sized slab for same-sized intermediates and
  release it at its last use instead of pinning it for the whole call —
  the HR output is ``scale^2`` x larger, so it never aliases the input
  (auto: on for accelerator backends, off on CPU where XLA does not
  implement donation).
  Donated inputs are CONSUMED — ``upscale`` only ever donates slabs the
  session itself staged; arrays passed straight to :meth:`serve_batch` are
  consumed when donation is on.

Stats split DISPATCH latency (time to enqueue a chunk) from COMPLETE
latency (dispatch -> result ready); throughput is computed over the
serving wall-clock span, so steady-state fps reflects the overlap.  A
synchronous caller (:meth:`serve_batch`) records identical dispatch and
complete values.

Compilation always happens on a zero dummy **in the dtype being served**,
inside the cache-miss path — so steady-state latency stats
(:meth:`SRSession.stats`) never include compile time, and a first batch in
a new dtype never pays a silent mid-serving compile.

``VideoStream`` (stream.py) is now a deprecated shim over a session pinned
to one plan, one bucket and ``pipeline_depth=1`` (the legacy blocking
behavior).

Since the :class:`~repro.engine.server.SRServer` redesign, the session no
longer owns a serving loop of its own: :meth:`SRSession.submit` queues a
request on an embedded single-model server (which runs the pipelined
dispatch/coalescing drain), and :meth:`SRSession.upscale` is a thin
synchronous shim over ``submit(frames).result()``.  The session keeps what
is per-model state: the plan/executor caches, the prepared weight stacks,
the staging buffer and the latency/throughput stats (recorded identically
whether a batch arrived through ``upscale``, ``submit`` or a stream).
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.executor import (
    PreparedStack,
    build_band_executor,
    build_stack_executor,
    output_spec,
    prepare_stack,
)
from repro.engine.plan import (
    PREFERRED_BAND_ROWS,
    SRPlan,
    check_layer_channels,
)
from repro.engine.spans import Spans

__all__ = [
    "SRSession",
    "PlanCache",
    "StreamStats",
    "bucket_batch",
]

# The server's spans and counters that stats() reports as <name>_p50_ms:
# submit (the caller's submit), queue_wait (admission -> the launch that
# carries a request's first frame), assemble (staging copy + device_put),
# device_ready (executor call's return -> result ready), complete
# (finalize, per-request slicing, future resolution, done-callbacks).
SPAN_STATS = ("submit", "queue_wait", "assemble", "device_ready", "complete")
# Reported by mesh sessions only: shard_put (inside assemble, a host slab
# put straight into the band-sharded executor's input sharding).
MESH_SPAN_STATS = ("shard_put",)


class StreamStats(dict):
    """Latency/throughput summary: frames, batches, fps, dispatch/complete
    p50/p95/p99/mean ms."""


def latency_stats(
    lat_ms: Sequence[float],
    frames: int,
    *,
    dispatch_ms: Optional[Sequence[float]] = None,
    total_s: Optional[float] = None,
    **extra,
) -> StreamStats:
    """Summarise recorded per-call latencies (compile time never included).

    ``lat_ms`` are COMPLETE latencies (dispatch -> result ready); the
    headline percentiles (``p50_ms``/``p95_ms``/``p99_ms``/``mean_ms``)
    come from them.  ``dispatch_ms`` (enqueue time only) populates the
    ``dispatch_*`` keys — for a synchronous caller both series are the
    same list, so the values are identical.  ``total_s`` is the serving
    wall-clock span: with pipelining, completes overlap, so fps is frames
    over the SPAN, not over the sum of latencies.  A clock too coarse to
    resolve any call reports ``fps=0.0``, not inf.
    """
    lat = np.asarray(lat_ms, dtype=np.float64)
    disp = lat if dispatch_ms is None else np.asarray(dispatch_ms, np.float64)
    if lat.size == 0:
        return StreamStats(
            frames=0, batches=0, fps=0.0,
            p50_ms=0.0, p95_ms=0.0, p99_ms=0.0, mean_ms=0.0,
            dispatch_p50_ms=0.0, dispatch_p99_ms=0.0, dispatch_mean_ms=0.0,
            **extra,
        )
    total = lat.sum() / 1e3 if total_s is None else float(total_s)
    if disp.size == 0:
        d50 = d99 = dmean = 0.0
    else:
        d50 = float(np.percentile(disp, 50))
        d99 = float(np.percentile(disp, 99))
        dmean = float(disp.mean())
    return StreamStats(
        frames=frames,
        batches=int(lat.size),
        fps=frames / total if total > 0 else 0.0,
        p50_ms=float(np.percentile(lat, 50)),
        p95_ms=float(np.percentile(lat, 95)),
        p99_ms=float(np.percentile(lat, 99)),
        mean_ms=float(lat.mean()),
        dispatch_p50_ms=d50,
        dispatch_p99_ms=d99,
        dispatch_mean_ms=dmean,
        **extra,
    )


def bucket_batch(n: int) -> int:
    """Round a batch size up to the next power of two.

    Bucketing bounds the number of compiled programs per plan at
    ``log2(max batch)`` while wasting at most 2x padding compute on a
    worst-case batch — the standard serving trade for heterogeneous
    request sizes.
    """
    if n < 1:
        raise ValueError(f"batch size {n} must be >= 1")
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class _CacheEntry:
    """A compiled executor plus the key facts ``cache_stats`` reports."""

    fn: Callable[[jax.Array], jax.Array]
    plan: SRPlan
    bucket: int
    dtype: str
    compile_s: float
    stack_key: tuple = ()
    donates: bool = False
    # replica index when the entry was compiled by a ReplicaRouter (mesh
    # serving); None for ordinary single-device executors
    replica: Optional[int] = None
    # the band-sharded executor's input sharding, which the server's
    # assembler puts host slabs straight into; None: the default device
    in_sharding: Optional[jax.sharding.Sharding] = None

    @property
    def jitted(self):
        """The executor's own jit wrapper (trace-count introspection)."""
        return getattr(self.fn, "jitted", None)


class PlanCache:
    """LRU cache of compiled executors keyed by ``(plan, bucket, dtype)``.

    ``get`` counts a hit (and refreshes recency) or a miss; ``put`` evicts
    the least-recently-used entry past ``capacity`` and counts the
    eviction.  Counters are cumulative over the cache's lifetime.
    ``on_evict(key, entry)`` fires for every evicted entry (including
    :meth:`clear`), so the owner can release per-entry resources — the
    session uses it to drop the evicted executor's reference on the
    device-resident :class:`~repro.engine.executor.PreparedStack`.
    """

    def __init__(self, capacity: int = 8, on_evict: Optional[Callable] = None):
        if capacity < 1:
            raise ValueError(f"capacity={capacity} must be >= 1")
        self.capacity = capacity
        self.on_evict = on_evict
        self._entries: "OrderedDict[tuple, _CacheEntry]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key) -> Optional[_CacheEntry]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry

    def _evict_oldest(self) -> None:
        k, e = self._entries.popitem(last=False)
        self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(k, e)

    def put(self, key, entry: _CacheEntry) -> None:
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while len(self._entries) > self.capacity:
            self._evict_oldest()

    def clear(self) -> None:
        """Evict every entry (counted, ``on_evict`` fired per entry)."""
        while self._entries:
            self._evict_oldest()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:  # does not touch the counters
        return key in self._entries

    def keys(self) -> List[tuple]:
        """Keys in LRU -> MRU order (eviction order)."""
        return list(self._entries)

    def entries(self) -> List[_CacheEntry]:
        """Entries in LRU -> MRU order."""
        return list(self._entries.values())

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "size": len(self._entries),
            "capacity": self.capacity,
            "hit_rate": self.hits / total if total else 0.0,
        }


@dataclasses.dataclass
class _StackRecord:
    """A refcounted device-resident PreparedStack shared by cache entries."""

    stack: PreparedStack
    refs: int
    prepare_s: float


class SRSession:
    """One serving endpoint: fixed weights + policy, any request shape.

    Construct directly from a layer stack, via :meth:`open` (model name ->
    weights through the registry), or via :meth:`from_plan` (pin an
    existing plan — the ``VideoStream`` compatibility path).
    """

    def __init__(
        self,
        layers,
        *,
        backend: str = "tilted",
        precision: str = "fp32",
        vertical_policy: str = "zero",
        tile_cols: Optional[int] = None,
        band_rows: Optional[int] = None,
        preferred_band_rows: int = PREFERRED_BAND_ROWS,
        scale: int = 3,
        clip: bool = True,
        cache_capacity: int = 8,
        max_bucket: Optional[int] = None,
        model: Optional[str] = None,
        pipeline_depth: int = 2,
        donate_frames: Optional[bool] = None,
        strict: bool = False,
        mesh=None,
        route: str = "least_loaded",
    ):
        layers = tuple(layers)
        if not layers:
            raise ValueError("layer stack is empty")
        if max_bucket is not None and max_bucket < 1:
            raise ValueError(f"max_bucket={max_bucket} must be >= 1")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth={pipeline_depth} must be >= 1 "
                "(1 = blocking, 2 = double-buffered dispatch)"
            )
        if cache_capacity < 1:
            raise ValueError(
                f"cache_capacity={cache_capacity} must be >= 1 "
                "(the session needs at least one live compiled executor)"
            )
        # mesh serving: a trivial (1, 1) mesh serves as a plain session
        self.mesh_spec = None
        self._router = None
        if mesh is not None:
            from repro.engine.sharding import MeshSpec  # lazy: no cycle

            spec = MeshSpec.coerce(mesh)
            if not spec.is_trivial:
                self.mesh_spec = spec
        self.layers = layers
        self.model = model
        self.backend = backend
        self.precision = precision
        self.vertical_policy = vertical_policy
        self.tile_cols = tile_cols
        self.band_rows = band_rows
        self.preferred_band_rows = preferred_band_rows
        self.scale = scale
        self.clip = clip
        self.max_bucket = max_bucket
        # pipeline_depth bounds in-flight chunks per request: 1 = blocking
        # (complete t before dispatching t+1), 2 = double buffering (the
        # paper's ping-pong line buffers), deeper = more latency hiding at
        # the cost of holding more bucket-sized slabs live.
        self.pipeline_depth = pipeline_depth
        # strict=True statically verifies every derived plan
        # (repro.analysis.plan_check) and refuses error-level findings
        # BEFORE anything compiles; degenerate one-giant-band fallbacks
        # are counted either way and surface in cache_stats()
        self.strict = bool(strict)
        self._degenerate_plans = 0
        # per-cache-key compile counter: an entry evicted and re-missed
        # compiles again — the recompile detector (repro.analysis
        # .program_audit) flags keys whose count exceeds one
        self._compile_counts: Dict[tuple, int] = {}
        # donate_frames=None resolves per-backend at first executor build:
        # XLA implements input-output aliasing on accelerators but not CPU
        # (donating there just warns and copies).
        self.donate_frames = donate_frames
        self._cache = PlanCache(cache_capacity, on_evict=self._on_evict)
        # device-resident prepared weights, refcounted by live cache
        # entries — prepared ONCE per (precision, backend), dropped when
        # the last entry using them is evicted (no weight leak)
        self._stacks: Dict[tuple, _StackRecord] = {}
        # derived-plan / output-dtype memos; bounded like the executor
        # cache so a long-lived endpoint under arbitrarily diverse
        # resolutions cannot grow memory monotonically
        self._memo_cap = 8 * cache_capacity
        self._plans: Dict[Tuple[int, int, int], SRPlan] = {}
        self._out_dtypes: Dict[tuple, np.dtype] = {}
        self._pinned: Optional[SRPlan] = None
        self._pinned_bucket: Optional[int] = None
        # one host-side staging buffer, reused across ragged tails (keyed
        # by (bucket, frame shape, dtype) — replaced when the shape moves)
        self._staging: Optional[Tuple[tuple, np.ndarray]] = None
        # host spans and counters of the serving path (engine/spans.py):
        # "launch" (the executor call) and "latency" (launch -> result
        # ready) feed the headline stats; the rest feed the *_p50_ms keys
        self.spans = Spans()
        self._span_s = 0.0
        self._frames = 0
        self._peak_inflight = 0
        # temporal delta serving (engine.temporal): partial-band dispatch
        # counters (bumped by the server at completion) plus the per-frame
        # reuse accounting DeltaSession maintains; the output cache is
        # created on first delta use
        self._band_rows_served = 0
        self._band_dispatches = 0
        self._temporal_counts: Dict[str, int] = {
            "frames": 0,
            "bands_total": 0,
            "bands_skipped": 0,
            "band_rows_total": 0,
            "band_rows_served": 0,
            "hbm_bytes_full": 0,
            "hbm_bytes_served": 0,
            "cover_violations": 0,
        }
        self._output_cache = None
        # the SRServer submit()/upscale() serve through: set by the first
        # server that hosts this session, else an embedded single-model
        # server created lazily on first submit
        self._server = None
        # mesh serving: the router owns per-replica compile caches + band-
        # sharded executors; built EAGERLY so a too-small device pool fails
        # at construction, not on the first request
        if self.mesh_spec is not None:
            from repro.engine.sharding import ReplicaRouter  # lazy: no cycle

            self._router = ReplicaRouter(
                self, self.mesh_spec, policy=route,
                cache_capacity=cache_capacity,
            )

    # ------------------------------------------------------------------
    # Constructors
    # ------------------------------------------------------------------
    @classmethod
    def open(
        cls,
        model: str = "abpn_x3",
        *,
        seed: int = 0,
        layers=None,
        scale: Optional[int] = None,
        clip: Optional[bool] = None,
        **kwargs,
    ) -> "SRSession":
        """Open a session on a registered SR model.

        Weights resolve through ``repro.models.registry.get_sr_model``:
        the spec's initialiser (seeded by ``seed``) unless an explicit
        trained ``layers`` stack is passed.  ``scale``/``clip`` default to
        the model config's values; everything else (backend, precision,
        vertical_policy, cache_capacity, pipeline_depth, ...) passes
        through to :class:`SRSession`.
        """
        from repro.models.registry import get_sr_model

        spec = get_sr_model(model)
        cfg = spec.config
        if layers is None:
            layers = spec.init(jax.random.PRNGKey(seed))
        return cls(
            layers,
            scale=cfg.scale if scale is None else scale,
            clip=cfg.clip if clip is None else clip,
            model=spec.name,
            **kwargs,
        )

    @classmethod
    def from_plan(
        cls,
        plan: SRPlan,
        layers,
        *,
        bucket: Optional[int] = None,
        cache_capacity: int = 8,
        **kwargs,
    ) -> "SRSession":
        """A session pinned to one plan (and optionally one batch bucket).

        This is what the deprecated ``VideoStream`` wraps: the plan's
        geometry/numerics are fixed, requests for any other LR shape are
        rejected, and ``bucket`` (when given) replaces power-of-two
        bucketing so the stream's exact batch size is the one compiled
        program.  ``kwargs`` (``pipeline_depth``, ``donate_frames``, ...)
        pass through to :class:`SRSession`.
        """
        session = cls(
            layers,
            backend=plan.backend,
            precision=plan.precision,
            vertical_policy=plan.vertical_policy,
            tile_cols=plan.tile_cols,
            band_rows=plan.band_rows,
            scale=plan.scale,
            clip=plan.clip,
            cache_capacity=cache_capacity,
            **kwargs,
        )
        check_layer_channels(session.layers, plan.in_channels, plan.scale)
        session._pinned = plan
        session._pinned_bucket = bucket
        session._plans[plan.lr_shape] = plan
        return session

    # ------------------------------------------------------------------
    # Plan + executor resolution
    # ------------------------------------------------------------------
    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def plan_for(self, lr_shape: Tuple[int, int, int]) -> SRPlan:
        """The session's plan for one LR frame shape (derived once, memoised
        — ``SRPlan.from_request``, re-banded to split over a mesh's band
        shards)."""
        lr_shape = tuple(int(x) for x in lr_shape)
        plan = self._plans.get(lr_shape)
        if plan is not None:
            return plan
        if self._pinned is not None:
            raise ValueError(
                f"session is pinned to LR shape {self._pinned.lr_shape}, "
                f"got {lr_shape}"
            )
        check_layer_channels(self.layers, lr_shape[2], self.scale)
        plan = SRPlan.from_request(
            lr_shape,
            num_layers=self.num_layers,
            band_rows=self.band_rows,
            tile_cols=self.tile_cols,
            vertical_policy=self.vertical_policy,
            backend=self.backend,
            precision=self.precision,
            scale=self.scale,
            clip=self.clip,
            preferred_band_rows=self.preferred_band_rows,
        )
        if self.mesh_spec is not None:
            plan = self._shardable_plan(plan)
        if plan.degenerate_bands:
            self._degenerate_plans += 1
        if self.strict:
            self._verify_plan(plan)
        self._memo_put(self._plans, lr_shape, plan)
        return plan

    def _shardable_plan(self, plan: SRPlan) -> SRPlan:
        """Make a derived plan legal for the session's mesh: re-band when
        the default decomposition does not split across the band shards;
        an EXPLICIT ``band_rows`` is the caller's decision and is rejected
        (never silently re-banded) when it cannot shard."""
        from repro.engine.sharding import check_shardable, ensure_shardable

        if self.band_rows is not None:
            err = check_shardable(plan, self.mesh_spec.band_shards)
            if err is not None:
                raise ValueError(
                    f"explicit band_rows={self.band_rows} cannot serve on "
                    f"mesh {self.mesh_spec.descriptor}: {err}"
                )
            return plan
        return ensure_shardable(
            plan, self.mesh_spec, self.preferred_band_rows
        )

    def _verify_plan(self, plan: SRPlan) -> None:
        """Strict-mode gate: statically verify the derived plan and raise
        :class:`~repro.analysis.findings.PlanVerificationError` on any
        error-level finding — BEFORE weight prep or compilation."""
        from repro.analysis import findings as _findings  # lazy: no cycle
        from repro.analysis import plan_check  # lazy: no cycle

        kwargs = {}
        if self.mesh_spec is not None:
            kwargs["band_shards"] = self.mesh_spec.band_shards
        errs = _findings.errors(plan_check.verify_plan(plan, **kwargs))
        if errs:
            raise _findings.PlanVerificationError(errs)

    def _memo_put(self, memo: dict, key, value) -> None:
        """Insert into a memo dict, evicting oldest entries past the cap
        (a pinned session never accumulates shapes, so pins are safe)."""
        memo[key] = value
        while len(memo) > self._memo_cap:
            try:
                memo.pop(next(iter(memo)))
            except (KeyError, StopIteration, RuntimeError):
                # concurrent server submits resolve plans outside the
                # server lock; losing the race for the oldest key just
                # means another thread evicted it — re-check the cap
                continue

    @staticmethod
    def serving_dtype(dtype) -> np.dtype:
        """The dtype a request ACTUALLY serves in: jax canonicalizes
        (float64 -> float32 without x64), so keying/compiling on the raw
        host dtype would duplicate programs and mislabel cache entries."""
        return np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype)))

    @classmethod
    def cache_key(cls, plan: SRPlan, bucket: int, dtype) -> tuple:
        return (plan, int(bucket), cls.serving_dtype(dtype).name)

    def _resolve_donate(self) -> bool:
        if self.donate_frames is not None:
            return bool(self.donate_frames)
        return jax.default_backend() != "cpu"

    def _acquire_stack(self, plan: SRPlan) -> Tuple[PreparedStack, tuple]:
        """The session's PreparedStack for this plan's numerics/backend,
        prepared on first use (blocking — NEVER inside serving latency)
        and refcounted per cache entry."""
        skey = plan.stack_key
        rec = self._stacks.get(skey)
        if rec is None:
            t0 = time.perf_counter()
            stack = prepare_stack(plan, self.layers)
            jax.block_until_ready(stack)
            rec = _StackRecord(
                stack=stack, refs=0, prepare_s=time.perf_counter() - t0
            )
            self._stacks[skey] = rec
        rec.refs += 1
        return rec.stack, skey

    def _release_stack(self, skey: tuple) -> None:
        rec = self._stacks.get(skey)
        if rec is None:
            return
        rec.refs -= 1
        if rec.refs <= 0:
            # last executor using these device buffers is gone — drop them
            del self._stacks[skey]

    def _on_evict(self, key, entry: _CacheEntry) -> None:
        self._release_stack(entry.stack_key)

    def clear_cache(self) -> None:
        """Evict every compiled executor AND release the device-resident
        prepared weights they pinned (frees accelerator memory; the next
        request re-prepares and recompiles)."""
        self._cache.clear()
        if self._router is not None:
            self._router.clear()

    def executor_for(
        self, plan: SRPlan, bucket: int, dtype
    ) -> Tuple[_CacheEntry, bool]:
        """The compiled executor for ``(plan, bucket, dtype)``.

        Cache miss prepares the weight stack (once per session numerics —
        shared and refcounted across entries) and compiles NOW, warmed on a
        zero dummy in the dtype that will actually be served, recording the
        compile seconds on the entry — so no later ``fn`` call on this key
        pays compilation or weight prep.  Returns ``(entry, compiled_now)``.

        On a mesh session the call routes to a replica's band-sharded
        executor instead (``entry.replica`` records which one).
        """
        if self._router is not None:
            return self._router.executor_for(plan, bucket, dtype)
        dtype = self.serving_dtype(dtype)
        key = self.cache_key(plan, bucket, dtype)
        entry = self._cache.get(key)
        if entry is not None:
            return entry, False
        stack, skey = self._acquire_stack(plan)
        try:
            donate = self._resolve_donate()
            # own jit per entry: evicting the entry drops the only
            # reference this layer holds to the compiled program (the
            # module-level shared jit would pin it for the process); a
            # re-miss re-acquires and re-times — fast when jax's internal
            # caches still hold the program
            fn = build_stack_executor(plan, stack, donate_frames=donate)
            dummy = jnp.zeros((bucket, *plan.lr_shape), dtype)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(dummy))
            compile_s = time.perf_counter() - t0
        except BaseException:
            # a failed build/compile must not strand the stack refcount —
            # otherwise the device-resident weights could never be freed
            self._release_stack(skey)
            raise
        entry = _CacheEntry(
            fn=fn,
            plan=plan,
            bucket=int(bucket),
            dtype=dtype.name,
            compile_s=compile_s,
            stack_key=skey,
            donates=donate,
        )
        self._compile_counts[key] = self._compile_counts.get(key, 0) + 1
        self._cache.put(key, entry)
        return entry, True

    def band_executor_for(
        self, plan: SRPlan, bucket: int, dtype
    ) -> Tuple[_CacheEntry, bool]:
        """The compiled partial-band executor for ``(plan, bucket, dtype)``
        — the temporal delta path's program:
        ``(bucket, rows, W, C) slabs + (bucket, 2) bounds -> HR bands``.

        Lives in the same :class:`PlanCache` under a ``"bands"``-suffixed
        key with the same refcounted weight-stack sharing, warmed on zero
        dummies like the frame path.  Never donates (band slabs are small
        and the splice reads the result immediately).  On a mesh session
        the program compiles locally, unsharded: a partial-band dispatch
        is below the granularity band sharding pays off at, and single-
        device vs sharded full-frame outputs are already bit-exact, so
        the splice guarantee holds transitively.
        """
        if plan.backend == "reference":
            raise ValueError(
                "partial-band serving needs a banded backend (tilted or "
                "kernel); the reference backend computes whole frames"
            )
        dtype = self.serving_dtype(dtype)
        key = (plan, int(bucket), dtype.name, "bands")
        entry = self._cache.get(key)
        if entry is not None:
            return entry, False
        from repro.engine.temporal.band_diff import band_input_rows

        stack, skey = self._acquire_stack(plan)
        try:
            fn = build_band_executor(plan, stack)
            rows = band_input_rows(
                plan.band_rows, plan.num_layers, plan.vertical_policy
            )
            dummy = jnp.zeros(
                (bucket, rows, plan.width, plan.in_channels), dtype
            )
            dbounds = jnp.zeros((bucket, 2), jnp.int32)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(dummy, dbounds))
            compile_s = time.perf_counter() - t0
        except BaseException:
            self._release_stack(skey)
            raise
        entry = _CacheEntry(
            fn=fn,
            plan=plan,
            bucket=int(bucket),
            dtype=dtype.name,
            compile_s=compile_s,
            stack_key=skey,
            donates=False,
        )
        self._compile_counts[key] = self._compile_counts.get(key, 0) + 1
        self._cache.put(key, entry)
        return entry, True

    def output_dtype(self, plan: SRPlan, dtype) -> np.dtype:
        """The dtype the compiled executor emits for ``dtype`` input
        (abstract eval — no compile, memoised), so degenerate paths —
        empty clips — return exactly what a real batch would."""
        dtype = self.serving_dtype(dtype)
        key = (plan, dtype.name)
        out = self._out_dtypes.get(key)
        if out is None:
            out = output_spec(plan, self.layers, 1, dtype).dtype
            self._memo_put(self._out_dtypes, key, out)
        return out

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        if self._pinned_bucket is not None:
            return self._pinned_bucket
        bucket = bucket_batch(n)
        if self.max_bucket is not None:
            # clamp DOWN to the largest power of two within the cap — the
            # cap is a ceiling (e.g. device memory), never exceeded
            cap = 1 << (self.max_bucket.bit_length() - 1)
            bucket = min(bucket, cap)
        return bucket

    def flatten_request(self, frames) -> Tuple[object, int, Optional[tuple]]:
        """Validate a request and flatten it to ``(N, H, W, C)``.

        Returns ``(flat, ndim, lead)`` — the flat frame batch (host numpy
        stays host, already cast to the serving dtype; device arrays pass
        through), the caller's original rank, and the ``(B, T)`` leading
        shape for rank-5 input.  Malformed input fails HERE with a clear
        ``ValueError`` naming the expected ``(..., H, W, C)`` layout —
        non-array objects, non-numeric dtypes, bad ranks and channel
        counts never reach plan derivation or the compiler.
        """
        if isinstance(frames, (np.ndarray, jax.Array)):
            arr = frames
        else:
            try:
                arr = np.asarray(frames)
            except Exception as e:
                raise ValueError(
                    "expected an array of frames with shape (..., H, W, C); "
                    f"got {type(frames).__name__}"
                ) from e
        dtype = arr.dtype
        if not (jnp.issubdtype(dtype, jnp.floating)
                or jnp.issubdtype(dtype, jnp.integer)
                or dtype == np.bool_):
            raise ValueError(
                "expected numeric frames with shape (..., H, W, C); "
                f"got dtype {dtype} (from {type(frames).__name__})"
            )
        if isinstance(arr, np.ndarray):
            # cast to the dtype jax will actually serve in (float64 ->
            # float32 without x64) BEFORE keying/staging, so one program
            # serves both spellings and chunks match the compiled dtype
            arr = arr.astype(self.serving_dtype(dtype), copy=False)
        lead: Optional[tuple] = None
        if arr.ndim == 3:
            flat = arr[None]
        elif arr.ndim == 4:
            flat = arr
        elif arr.ndim == 5:
            lead = arr.shape[:2]
            flat = arr.reshape(arr.shape[0] * arr.shape[1], *arr.shape[2:])
        else:
            raise ValueError(
                "expected (H, W, C), (T, H, W, C) or (B, T, H, W, C) frames, "
                f"got shape {tuple(arr.shape)}"
            )
        ci = getattr(self.layers[0], "ci", None)
        if ci is not None and flat.shape[-1] != ci:
            raise ValueError(
                f"frames have {flat.shape[-1]} channels in the trailing "
                f"(..., H, W, C) axis; this session's layer stack expects "
                f"C={ci}"
            )
        return flat, arr.ndim, lead

    def submit(self, frames, *, priority: int = 0,
               deadline: Optional[float] = None,
               timeout: Optional[float] = None):
        """Queue a request on the session's embedded server; returns an
        :class:`~repro.engine.server.SRFuture` immediately.

        The request dispatches when the server's drain loop next turns
        over (``future.result()`` drives it), coalescing with any other
        queued requests that share this session's ``(plan, dtype)`` key.
        If an :class:`~repro.engine.server.SRServer` hosts this session,
        the request goes through THAT server (one scheduler + one lock
        govern all traffic into the session); otherwise an embedded
        single-model server is created on first use.  ``deadline``
        (absolute monotonic seconds) / ``timeout`` (relative) bound the
        request's QUEUED lifetime — see ``SRServer.submit``.
        """
        return self._host_server().submit_for(
            self, frames, priority=priority,
            deadline=deadline, timeout=timeout)

    def _host_server(self):
        """The server this session serves through — the hosting
        :class:`~repro.engine.server.SRServer` if one registered itself,
        else an embedded single-model server created on first use."""
        if self._server is None:
            from repro.engine.server import SRServer  # lazy: avoids a cycle

            # (SRServer.__init__ also registers itself on the session —
            # the assignment is the same object, stated explicitly)
            self._server = SRServer({self.model or "session": self})
        return self._server

    def upscale(self, frames) -> jax.Array:
        """Super-resolve frames of any supported rank (blocking).

        ``(H, W, C)`` -> ``(sH, sW, C)``; ``(T, H, W, C)`` ->
        ``(T, sH, sW, C)``; ``(B, T, H, W, C)`` -> ``(B, T, sH, sW, C)``.
        A thin synchronous shim over ``submit(frames).result()``: the
        flattened batch is served in bucket-sized dispatches through the
        server's pipelined drain (up to ``pipeline_depth`` in flight;
        host numpy input staged per chunk via the one reused staging
        buffer + ``jax.device_put``), padded outputs are trimmed, and only
        real frames count in :meth:`stats`.  The caller's array is never
        donated — only server-staged slabs.
        """
        return self.submit(frames).result()

    def serve_batch(
        self, plan: SRPlan, frames: jax.Array, real_frames: Optional[int] = None
    ) -> jax.Array:
        """Run ONE pre-bucketed batch through the plan's executor
        synchronously, recording its steady-state latency (a cache miss
        compiles on a dummy first, outside the timed region).  Dispatch and
        complete latency are the same recorded value — a synchronous call
        is not "dispatched" until its result is ready.  ``real_frames``
        counts only that many leading frames in :meth:`stats` — the rest
        are padding; the full batch is returned.  When frame donation is
        active, ``frames`` is CONSUMED by the call.
        """
        n_real = frames.shape[0] if real_frames is None else real_frames
        entry, _ = self.executor_for(plan, frames.shape[0], frames.dtype)
        with self.spans.span("launch") as launch:
            hr = entry.fn(frames)
            jax.block_until_ready(hr)
        self.spans.record("latency", launch.ms)
        self._span_s += launch.ms / 1e3
        self._frames += n_real
        self._peak_inflight = max(self._peak_inflight, 1)
        return hr

    def _staging_for(self, bucket: int, frame_shape, dtype) -> np.ndarray:
        """One reusable host buffer for staging ragged/coalesced host
        dispatches (no fresh bucket-sized allocation per tail); the
        server's assembler fills it and ships it with ``device_put``."""
        key = (bucket, tuple(frame_shape), np.dtype(dtype).str)
        if self._staging is None or self._staging[0] != key:
            self._staging = (key, np.zeros((bucket, *frame_shape), dtype))
        return self._staging[1]

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------
    @property
    def _lat_ms(self):
        """Back-compat alias: the complete-latency series."""
        return self.spans.series("latency")

    def cache_stats(self) -> dict:
        """Compile-cache counters plus per-entry compile metadata.

        ``hits``/``misses``/``evictions`` are cumulative; ``entries`` lists
        live entries in LRU -> MRU order, each with its plan shape, batch
        bucket, serving dtype and measured compile seconds.  ``stacks``
        lists the device-resident prepared weight stacks with their entry
        refcounts, one-time prepare seconds and resident bytes.
        ``degenerate_plans`` counts derived one-giant-band fallbacks.
        """
        stats = self._cache.stats()
        stats["recompiles"] = sum(
            c - 1 for c in self._compile_counts.values() if c > 1
        )
        stats["degenerate_plans"] = self._degenerate_plans
        stats["entries"] = [
            {
                "lr_shape": list(e.plan.lr_shape),
                "backend": e.plan.backend,
                "precision": e.plan.precision,
                "band_rows": e.plan.band_rows,
                "bucket": e.bucket,
                "dtype": e.dtype,
                "compile_s": e.compile_s,
                "donates": e.donates,
            }
            for e in self._cache.entries()
        ]
        stats["stacks"] = [
            {
                "precision": k[0],
                "backend": k[1],
                "refs": rec.refs,
                "prepare_s": rec.prepare_s,
                "resident_bytes": rec.stack.nbytes(),
            }
            for k, rec in self._stacks.items()
        ]
        return stats

    def stats(self, **extra) -> StreamStats:
        """Steady-state serving stats — compile and weight-prep time are
        never included (both happen inside the cache-miss path, outside
        the timed span).  Percentiles split dispatch (enqueue) from
        complete (result ready); ``fps`` is real frames over the serving
        wall-clock span, so pipelined overlap shows up as throughput.
        ``<series>_p50_ms`` are the medians of the server's host spans
        and counters (:data:`SPAN_STATS`; those of
        :data:`MESH_SPAN_STATS` on mesh sessions only), 0.0 before the
        first value."""
        if self._temporal_counts["frames"] and "temporal" not in extra:
            extra["temporal"] = self.temporal_stats()
        mesh = MESH_SPAN_STATS if self._router is not None else ()
        for name in SPAN_STATS + mesh:
            v = self.spans.values(name)
            extra[f"{name}_p50_ms"] = float(np.percentile(v, 50)) if v else 0.0
        return latency_stats(
            self.spans.values("latency"),
            self._frames,
            dispatch_ms=self.spans.values("launch"),
            total_s=self._span_s,
            peak_inflight=self._peak_inflight,
            **extra,
        )

    def output_cache(self, max_bytes: Optional[int] = None):
        """The session's HR output-band cache (temporal delta serving),
        created on first use.  ``max_bytes`` only applies at creation —
        later callers share whatever bound the first one set."""
        if self._output_cache is None:
            from repro.engine.temporal.output_cache import (  # lazy: no cycle
                DEFAULT_CACHE_BYTES,
                OutputBandCache,
            )

            self._output_cache = OutputBandCache(
                max_bytes=DEFAULT_CACHE_BYTES if max_bytes is None
                else max_bytes
            )
        return self._output_cache

    def temporal_stats(self) -> dict:
        """Delta-serving counters (the ``temporal`` section of
        :meth:`stats`).

        ``reuse_ratio`` is spliced-from-cache bands over all bands of
        delta-served frames; ``band_rows_*`` count LR rows of conv-stack
        compute (``served / total`` is the compute fraction the delta
        path actually ran).  ``effective_hbm_bytes_per_frame`` models
        the paper's DRAM-traffic metric for the delta path: the LR slab
        bytes dispatched plus the HR band bytes written, per frame —
        weights excluded (they are resident either way) — next to
        ``full_hbm_bytes_per_frame``, the same model for full
        re-upscale.
        """
        t = self._temporal_counts
        frames = t["frames"]
        total = t["bands_total"]
        out = {
            "frames": frames,
            "bands_total": total,
            "bands_skipped": t["bands_skipped"],
            "reuse_ratio": t["bands_skipped"] / total if total else 0.0,
            "band_rows_total": t["band_rows_total"],
            "band_rows_served": t["band_rows_served"],
            "band_dispatches": self._band_dispatches,
            # server-side truth: band-rows across ALL partial dispatches
            # (any submit_bands caller), vs the delta accounting above
            "band_rows_dispatched": self._band_rows_served,
            "effective_hbm_bytes_per_frame":
                t["hbm_bytes_served"] / frames if frames else 0.0,
            "full_hbm_bytes_per_frame":
                t["hbm_bytes_full"] / frames if frames else 0.0,
            "cover_violations": t["cover_violations"],
        }
        if self._output_cache is not None:
            out["cache"] = self._output_cache.stats()
        return out

    def sharding_stats(self) -> Optional[dict]:
        """Mesh routing stats (replica dispatch balance, per-replica
        caches, halo bytes per frame); ``None`` on an unsharded session."""
        if self._router is None:
            return None
        return self._router.stats()

    def reset_stats(self) -> None:
        self.spans.reset()
        self._span_s = 0.0
        self._frames = 0
        self._peak_inflight = 0
        self._band_rows_served = 0
        self._band_dispatches = 0
        for k in self._temporal_counts:
            self._temporal_counts[k] = 0
