"""Batched SR execution engine (the serving subsystem).

``SRServer`` (server.py) is the serving front door:
``SRServer.open(models...)`` hosts one or more named sessions,
``server.submit(frames, model=..., priority=...)`` returns an
:class:`SRFuture`, and ``server.stream(...)`` is an async generator for
frame-at-a-time live video.  A micro-batching scheduler (scheduler.py)
coalesces concurrent requests that share a ``(model, plan, dtype)`` key
into single bucket-sized dispatches (real frames instead of padding) and
enforces a bounded queue with backpressure (``max_inflight_frames``,
block-or-reject admission).

``SRSession`` (session.py) is the per-model layer underneath:
``SRSession.open(model)`` resolves weights through the model registry and
``session.upscale(frames)`` — now a thin synchronous shim over
``session.submit(frames).result()`` — serves any ``(H, W, C)`` /
``(T, H, W, C)`` / ``(B, T, H, W, C)`` request, deriving the
:class:`SRPlan` per resolution (``SRPlan.from_request``), bucketing
batches to powers of two, and compiling executors on demand into an LRU
:class:`PlanCache` (``session.cache_stats()``).  Serving is pipelined:
weights are prepared once per session into a device-resident
:class:`PreparedStack`, dispatches keep up to ``pipeline_depth`` chunks in
flight (double buffering), and executors can donate the frame slab back to
XLA (``donate_frames``).

Schedules are TUNED, not hard-coded: the autotuner (autotune.py) sweeps
the legal (band_rows, pipeline_depth, bucket policy) space per
configuration — roofline-pruned, then compiled and measured — and
persists winners in a JSON :class:`TuningDB`; sessions consult it on
cold start (``SRSession.open(..., autotune="off"|"cached"|"full")``,
``session.tuning_stats()``).

Serving is MESH-AWARE (sharding/): ``SRSession.open(..., mesh=(R, S))``
band-shards every executor over a ``bands`` device axis (``shard_map`` +
ppermute halo exchange at shard edges, bit-exact vs single-device) and
routes coalesced dispatches across ``R`` replicas
(:class:`ReplicaRouter`: round-robin / least-loaded, per-replica compile
caches; ``session.sharding_stats()``).

Serving is DELTA-AWARE for video (temporal/): ``server.stream(...,
delta=True)`` (or a :class:`DeltaSession` directly) band-diffs each
frame against the previous one, dilates the changed bands by the halo
reach, dispatches only the dirty bands as partial-band dispatches
(``submit_bands`` -> ``Dispatch.band_subset`` through the same
scheduler), and splices clean bands from a bounded refcounted
:class:`OutputBandCache` keyed by receptive-field window digest —
bit-exact vs full re-upscale (``session.stats()['temporal']``).

Underneath: ``SRPlan`` (plan.py) describes one execution — geometry,
numerics, boundary policy, backend — and ``build_executor``/``run``
(executor.py) compile it into a single jitted call over a batch of LR
frames.  ``VideoStream`` (stream.py) is a deprecated fixed-batch shim over
a pinned session; ``models.abpn.apply_abpn(method=...)`` is an older shim
over ``run``.
"""

from repro.engine.autotune import (
    PlanTuner,
    TuningDB,
    TuningEntry,
    TuningKey,
    tune,
)
from repro.engine.executor import (
    PreparedStack,
    build_executor,
    build_stack_executor,
    executor_artifacts,
    output_spec,
    plan_cost,
    prepare_layers,
    prepare_stack,
    run,
    sr_epilogue,
    sr_features,
)
from repro.engine.plan import (
    BACKENDS,
    PRECISIONS,
    VERTICAL_POLICIES,
    SRPlan,
    derive_band_rows,
    derive_tile_cols,
    legal_band_rows,
    make_plan,
    shardable_band_rows,
)
from repro.engine.scheduler import (
    DeadlineExceededError,
    MicroBatchScheduler,
    QueueFullError,
    RequestShedError,
)
from repro.engine.server import (
    DEGRADE_LADDER,
    DegradePolicy,
    RequestCancelledError,
    SRFuture,
    SRServer,
)
from repro.engine.session import (
    AUTOTUNE_MODES,
    PlanCache,
    SRSession,
    StreamStats,
    bucket_batch,
)
from repro.engine.sharding import (
    ROUTE_POLICIES,
    MeshSpec,
    ReplicaRouter,
    ShardedPlan,
    build_sharded_executor,
)
from repro.engine.stream import VideoStream
from repro.engine.temporal import DeltaSession, OutputBandCache

__all__ = [
    "SRServer",
    "SRFuture",
    "MicroBatchScheduler",
    "QueueFullError",
    "DeadlineExceededError",
    "RequestShedError",
    "RequestCancelledError",
    "DegradePolicy",
    "DEGRADE_LADDER",
    "DeltaSession",
    "OutputBandCache",
    "SRSession",
    "PlanCache",
    "bucket_batch",
    "SRPlan",
    "make_plan",
    "derive_band_rows",
    "derive_tile_cols",
    "legal_band_rows",
    "AUTOTUNE_MODES",
    "PlanTuner",
    "TuningDB",
    "TuningEntry",
    "TuningKey",
    "tune",
    "BACKENDS",
    "PRECISIONS",
    "VERTICAL_POLICIES",
    "build_executor",
    "build_stack_executor",
    "executor_artifacts",
    "output_spec",
    "plan_cost",
    "prepare_layers",
    "prepare_stack",
    "PreparedStack",
    "run",
    "sr_epilogue",
    "sr_features",
    "shardable_band_rows",
    "MeshSpec",
    "ShardedPlan",
    "ReplicaRouter",
    "ROUTE_POLICIES",
    "build_sharded_executor",
    "VideoStream",
    "StreamStats",
]
