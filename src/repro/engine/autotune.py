"""Roofline-guided plan autotuner — sweep the legal schedule space, keep
the winners.

The paper's accelerator hits 1080p@60fps on ONE hand-tuned schedule
(60-row tilted bands, double-buffered line memories).  The software
engine inherited those constants for every backend, resolution, precision
and batch — and the benchmark record shows it leaves throughput on the
table (bucket choice alone swings CPU frames/s by ~1.6x, and depth-2
pipelining *hurts* p50 latency on CPU).  This module makes the schedule a
measured decision instead of a constant, the measured-cost-model-driven
kernel search the embedded-GPU SR accelerators (Zhao et al., PAPERS.md)
use to beat hand-tuned schedules:

1. **Enumerate** the legal candidate space for a (backend, lr_shape,
   precision, request batch) configuration:

   * ``band_rows`` — every legal divisor near the preferred height
     (:func:`~repro.engine.plan.legal_band_rows`)... but ONLY for the
     ``halo`` vertical policy, where band decomposition is bit-exact
     invariant (each band recomputes its true receptive field).  Under
     ``zero``/``replicate`` the band boundary is an approximation, so
     retuning ``band_rows`` would change numerics — those plans keep the
     default, and the tuner says so.
   * ``pipeline_depth`` in ``{1..4}`` — in-flight dispatches per request.
   * bucket rounding policy — round the batch up to a power of two
     (bounded program count) vs compile the exact batch (zero padding
     waste).  Both are numerics-safe: padded frames are computed
     independently and trimmed.

2. **Score analytically first.**  :func:`predict_cost` is a pure-math
   roofline (per-frame FLOPs + HBM bytes from plan geometry — the halo
   recompute factor ``(R+2L)/R``, the cache-residency of the per-band
   working set, the padding waste of the bucket) — no compilation.
   Candidates whose predicted frame time exceeds ``prune_ratio`` (1.5x)
   of the roofline-best are pruned before ever being compiled.  The
   default schedule always survives, so the measured baseline — and the
   tuned >= default guarantee — is never lost to the model being wrong.

3. **Compile + measure the survivors.**  Each surviving (band_rows,
   bucket) compiles ONE executor over a shared
   :class:`~repro.engine.executor.PreparedStack` — never touching any
   session's ``PlanCache`` — and each depth is measured with the same
   bounded in-flight dispatch loop the server runs.  The measured pass is
   the arbiter: the analytic model proposes, wall-clock disposes (ties
   within ``tie_tol`` prefer the shallower pipeline and the default
   schedule — simpler wins when measurement can't separate them).

4. **Persist.**  Winners land in a JSON :class:`TuningDB`
   (``~/.cache/repro-sr/tuning.json``, ``REPRO_SR_TUNING_DB`` overrides)
   keyed like the ``PlanCache`` — the full plan configuration plus the
   batch bucket — and stamped with schema version, jax backend and device
   kind so entries from another schema/machine are ignored rather than
   misapplied.  Writes are atomic (temp file + ``os.replace``) and the DB
   is bounded (oldest entries evicted past ``capacity``).

Serving consults the DB through :class:`PlanTuner`:
``SRPlan.from_request(..., tuner=)`` asks it for a measured ``band_rows``;
``SRSession.open(model, autotune="off"|"cached"|"full")`` controls the
cold-start policy (``"cached"`` = lookup only, never measure in the
serving path; ``"full"`` = tune-and-persist on a miss);
``session.tuning_stats()`` reports hits/misses/fallbacks.

Pre-warm the DB offline::

    PYTHONPATH=src python -m repro.engine.autotune --sweep
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import tempfile
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.engine.plan import SRPlan, derive_band_rows, legal_band_rows

__all__ = [
    "SCHEMA_VERSION",
    "DB_ENV_VAR",
    "default_db_path",
    "TuningKey",
    "TuningEntry",
    "TuningDB",
    "RooflinePeaks",
    "PEAKS_BY_DEVICE_KIND",
    "predict_cost",
    "Candidate",
    "enumerate_candidates",
    "measure_schedule",
    "tune",
    "PlanTuner",
]

# Bump when the entry layout or the meaning of a tuned knob changes —
# loaders ignore any DB written under a different schema (stale entries
# must never be misapplied to a new engine).
# v2: entries carry a topology stamp (device_count + mesh_shape) so a
# schedule tuned on one device layout is rejected on another.
SCHEMA_VERSION = 2

DB_ENV_VAR = "REPRO_SR_TUNING_DB"

# Tunable pipeline depths: 1 = blocking, 2 = the paper's ping-pong double
# buffering, 3-4 = deeper latency hiding (more live slabs).
DEPTHS = (1, 2, 3, 4)

# A candidate within this fraction of the measured best is a TIE — the
# simpler schedule (shallower pipeline, default band/bucket) wins it.
TIE_TOL = 0.03


def default_db_path() -> str:
    """``$REPRO_SR_TUNING_DB`` if set, else ``~/.cache/repro-sr/tuning.json``."""
    env = os.environ.get(DB_ENV_VAR)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro-sr", "tuning.json"
    )


def device_kind() -> str:
    """The kind string of device 0 — part of every entry's validity stamp
    (a schedule tuned on one device class must not steer another).  A
    backend that cannot name its device raises; there is no stand-in."""
    import jax

    return str(jax.devices()[0].device_kind)


# ----------------------------------------------------------------------
# Keys + entries + the persistent DB
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TuningKey:
    """What a tuning decision is FOR: every plan field that is not a
    tunable knob, plus the request batch the bucket policy was tuned at —
    exactly the ``PlanCache`` key shape minus the knobs themselves."""

    backend: str
    precision: str
    vertical_policy: str
    height: int
    width: int
    channels: int
    num_layers: int
    tile_cols: int
    scale: int
    clip: bool
    batch: int  # the request batch size the sweep was run for

    @classmethod
    def from_plan(cls, plan: SRPlan, batch: int) -> "TuningKey":
        return cls(
            backend=plan.backend,
            precision=plan.precision,
            vertical_policy=plan.vertical_policy,
            height=plan.height,
            width=plan.width,
            channels=plan.in_channels,
            num_layers=plan.num_layers,
            tile_cols=plan.tile_cols,
            scale=plan.scale,
            clip=plan.clip,
            batch=int(batch),
        )

    def encode(self) -> str:
        return (
            f"{self.backend}|{self.precision}|{self.vertical_policy}"
            f"|{self.height}x{self.width}x{self.channels}"
            f"|L{self.num_layers}|T{self.tile_cols}|s{self.scale}"
            f"|clip{int(self.clip)}|b{self.batch}"
        )

    def config_encode(self) -> str:
        """The key minus the batch — the fallback grouping (a nearby
        batch's tuned schedule beats the untuned default)."""
        return self.encode().rsplit("|b", 1)[0]


@dataclasses.dataclass
class TuningEntry:
    """One tuned schedule: the winning knobs plus the evidence and the
    validity stamp."""

    band_rows: int
    pipeline_depth: int
    bucket: int
    bucket_policy: str  # "pow2" | "exact"
    predicted_ms: float  # analytic roofline ms per real frame (winner)
    measured_ms: float  # measured ms per real frame (winner)
    default_ms: float  # measured ms per real frame (default schedule)
    speedup: float  # default_ms / measured_ms (>= 1 by construction)
    jax_backend: str
    device_kind: str
    created: float  # unix seconds
    # topology stamp: schedules are measured on ONE device layout and are
    # invalid on any other (a 1-device winner says nothing about halo
    # exchange cost on a 2x4 mesh).  mesh_shape is "RxS" (replicas x band
    # shards); unsharded sessions are "1x1".
    device_count: int = 1
    mesh_shape: str = "1x1"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> Optional["TuningEntry"]:
        try:
            return cls(**{f.name: d[f.name]
                          for f in dataclasses.fields(cls)})
        except (KeyError, TypeError):
            return None  # malformed entry — treat as absent


class TuningDB:
    """The persistent winner store: one JSON file, atomic writes, bounded
    size, schema/backend/device validity filtering on read.

    Layout::

        {"schema": 2, "entries": {"<key.encode()>": {<TuningEntry>}, ...}}

    A file written under a different ``SCHEMA_VERSION`` is ignored
    wholesale (``stale_schema`` records that it happened); an entry
    stamped with a different jax backend, device kind, device count or
    mesh shape is ignored per-lookup.  ``put`` keeps insertion order and evicts the oldest
    entries past ``capacity``; ``save`` writes a temp file in the target
    directory and ``os.replace``\\ s it — readers never see a torn file.
    """

    def __init__(self, path: Optional[str] = None, capacity: int = 256):
        if capacity < 1:
            raise ValueError(f"capacity={capacity} must be >= 1")
        self.path = path or default_db_path()
        self.capacity = capacity
        self.stale_schema = False
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError):
            return  # missing or torn file — start empty
        if not isinstance(raw, dict) or raw.get("schema") != SCHEMA_VERSION:
            self.stale_schema = True
            return  # another engine's DB — never misapply its schedules
        entries = raw.get("entries")
        if isinstance(entries, dict):
            for k, v in entries.items():
                if isinstance(v, dict):
                    self._entries[k] = v

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self) -> List[str]:
        return list(self._entries)

    def get(
        self,
        key: TuningKey,
        *,
        device_count: Optional[int] = None,
        mesh_shape: str = "1x1",
    ) -> Optional[TuningEntry]:
        """The valid entry for ``key``, or None (wrong backend/device/
        topology or malformed entries are invalid, not errors).

        ``device_count`` defaults to the live ``jax.device_count()``;
        ``mesh_shape`` is the consumer's serving topology ("RxS") — an
        entry stamped with any other layout is rejected, never silently
        reused.
        """
        raw = self._entries.get(key.encode())
        if raw is None:
            return None
        entry = TuningEntry.from_dict(raw)
        if entry is None:
            return None
        import jax

        if (entry.jax_backend != jax.default_backend()
                or entry.device_kind != device_kind()):
            return None
        if device_count is None:
            device_count = jax.device_count()
        if (entry.device_count != int(device_count)
                or entry.mesh_shape != mesh_shape):
            return None
        return entry

    def get_nearest_batch(
        self,
        key: TuningKey,
        *,
        device_count: Optional[int] = None,
        mesh_shape: str = "1x1",
    ) -> Optional[Tuple[TuningEntry, int]]:
        """The valid entry matching ``key``'s configuration at the NEAREST
        tuned batch (the fallback when the exact batch was never swept);
        returns ``(entry, tuned_batch)`` or None."""
        prefix = key.config_encode() + "|b"
        best: Optional[Tuple[int, int, str]] = None
        for k in self._entries:
            if not k.startswith(prefix):
                continue
            try:
                b = int(k[len(prefix):])
            except ValueError:
                continue
            rank = (abs(b - key.batch), b)
            if best is None or rank < best[:2]:
                best = (*rank, k)
        if best is None:
            return None
        entry = self.get(
            dataclasses.replace(key, batch=best[1]),
            device_count=device_count, mesh_shape=mesh_shape,
        )
        return (entry, best[1]) if entry is not None else None

    def put(self, key: TuningKey, entry: TuningEntry) -> None:
        enc = key.encode()
        self._entries.pop(enc, None)
        self._entries[enc] = entry.to_dict()
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)

    def save(self) -> None:
        """Atomic write: temp file next to the target + ``os.replace``."""
        payload = {"schema": SCHEMA_VERSION, "entries": dict(self._entries)}
        directory = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(directory, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


# ----------------------------------------------------------------------
# The analytic roofline (scoring WITHOUT compiling)
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RooflinePeaks:
    """Peak compute/bandwidth + cache budget the predictor ranks against.

    The ratios set where the model places the compute/memory knee and when
    a band's working set spills; the measured pass arbitrates between
    candidates.  :meth:`detect` reads :data:`PEAKS_BY_DEVICE_KIND`.
    """

    flops_per_s: float
    hbm_bytes_per_s: float
    cache_bytes: float

    @classmethod
    def detect(cls) -> "RooflinePeaks":
        kind = device_kind()
        try:
            return PEAKS_BY_DEVICE_KIND[kind]
        except KeyError:
            raise ValueError(
                f"no roofline peaks for device_kind {kind!r}; add its "
                "published peaks to autotune.PEAKS_BY_DEVICE_KIND"
            ) from None


# Keyed by ``jax.devices()[0].device_kind``.  A kind not listed is an error.
PEAKS_BY_DEVICE_KIND = {
    # Google Cloud "TPU v5e" page: 197 TFLOP/s bf16, 819 GB/s HBM; 128 MiB
    # of VMEM per TensorCore (jax.experimental.pallas.tpu's chip table).
    "TPU v5 lite": RooflinePeaks(197e12, 819e9, 128 << 20),
    # The CPU backend runs the interpret-mode test path only.  Not a
    # published peak: a ranking guess for a few-core SIMD host (tens of
    # GFLOP/s and GB/s, ~1 MiB of L2 per core for the band working set).
    "cpu": RooflinePeaks(5e10, 2e10, 1 << 20),
}


def _layer_channels(layers: Sequence) -> List[Tuple[int, int]]:
    chans = []
    for l in layers:
        ci = getattr(l, "ci", None)
        co = getattr(l, "co", None)
        if ci is None or co is None:  # duck-typed stacks: fall back to w
            ci, co = int(l.w.shape[2]), int(l.w.shape[3])
        chans.append((int(ci), int(co)))
    return chans


def predict_cost(
    plan: SRPlan,
    layers: Sequence,
    bucket: int,
    real_frames: int,
    peaks: Optional[RooflinePeaks] = None,
) -> dict:
    """Analytic roofline prediction for serving ``real_frames`` frames in
    one ``bucket``-sized dispatch of ``plan`` — pure geometry, NO
    compilation (this is what prunes the candidate space).

    Per band, every fused layer computes ``rows_c`` rows (``R`` for
    zero/replicate, ``R + 2L`` for halo — the recompute margin the paper
    trades DRAM traffic against).  FLOPs are the 3x3 MACs over those
    rows.  HBM bytes charge the frame in/out and the weights always, and
    the inter-layer feature maps only when the band working set exceeds
    the cache budget (cache-resident bands stream through on-chip, the
    whole point of banding).  Padded bucket slots compute like real
    frames, so the per-real-frame time scales by ``bucket/real_frames`` —
    the waste the exact-bucket policy removes.
    """
    if peaks is None:
        peaks = RooflinePeaks.detect()
    chans = _layer_channels(layers)
    H, W = plan.height, plan.width
    R, L, B = plan.band_rows, plan.num_layers, plan.num_bands
    rows_c = R + 2 * L if plan.vertical_policy == "halo" else R
    dsize = 2 if plan.precision == "bf16" else 4
    max_ch = max(max(ci, co) for ci, co in chans)

    flops = B * sum(2 * 9 * rows_c * W * ci * co for ci, co in chans)
    # epilogue: anchor add + pixel shuffle over the HR frame
    flops += 4 * H * W * plan.in_channels * plan.scale ** 2

    weight_bytes = sum(9 * ci * co * dsize for ci, co in chans)
    io_bytes = (H * W * plan.in_channels * 4
                + H * W * plan.in_channels * plan.scale ** 2 * 4)
    hbm = io_bytes + weight_bytes
    working_set = rows_c * W * max_ch * dsize
    if working_set > peaks.cache_bytes:
        # the band no longer fits on-chip: every fused layer's feature
        # map round-trips memory
        hbm += B * sum(2 * rows_c * W * co * dsize for _, co in chans)

    frame_s = max(flops / peaks.flops_per_s, hbm / peaks.hbm_bytes_per_s)
    ms_per_frame = frame_s * 1e3 * bucket / max(real_frames, 1)
    return {
        "flops_per_frame": int(flops),
        "hbm_bytes_per_frame": int(hbm),
        "working_set_bytes": int(working_set),
        "ms_per_frame": float(ms_per_frame),
    }


# ----------------------------------------------------------------------
# Candidate space + measurement
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Candidate:
    """One point of the schedule space, carrying its scores through the
    sweep."""

    band_rows: int
    bucket: int
    pipeline_depth: int
    is_default: bool = False
    predicted_ms: float = math.nan
    measured_ms: float = math.nan
    pruned: bool = False


def band_rows_is_tunable(plan: SRPlan) -> bool:
    """Whether ``band_rows`` may differ from the default WITHOUT changing
    numerics: only the ``halo`` policy recomputes each band's true
    receptive field (bit-exact for any legal decomposition — asserted in
    tests/test_autotune.py); zero/replicate band boundaries are
    approximations, so their band height is part of the numerics, not the
    schedule."""
    return plan.vertical_policy == "halo"


def _pow2_bucket(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def enumerate_candidates(
    plan: SRPlan,
    batch: int,
    *,
    depths: Sequence[int] = DEPTHS,
    max_band_candidates: int = 4,
) -> List[Candidate]:
    """The legal candidate grid for one configuration.

    ``band_rows`` spans the nearest ``max_band_candidates`` legal
    decompositions (halo plans only — see :func:`band_rows_is_tunable`);
    the bucket axis is the two rounding policies (power-of-two vs exact);
    depth spans ``depths``.  Exactly one candidate ``is_default`` — the
    schedule today's hard-coded constants would run (default band, pow2
    bucket, depth 2) — and it is never pruned.
    """
    default_band = derive_band_rows(plan.height)
    if band_rows_is_tunable(plan):
        bands = legal_band_rows(plan.height)[:max_band_candidates]
        if default_band not in bands:
            bands.append(default_band)
    else:
        bands = [plan.band_rows]  # pinned: numerics, not schedule
    pow2 = _pow2_bucket(batch)
    buckets = sorted({pow2, int(batch)})
    default_depth = 2  # SRSession's constructor default
    depths = sorted(set(int(d) for d in depths))
    if default_depth not in depths:
        depths.append(default_depth)
    out = []
    for band in bands:
        for bucket in buckets:
            for depth in depths:
                out.append(Candidate(
                    band_rows=band,
                    bucket=bucket,
                    pipeline_depth=depth,
                    is_default=(band == (default_band
                                         if band_rows_is_tunable(plan)
                                         else plan.band_rows)
                                and bucket == pow2
                                and depth == default_depth),
                ))
    return out


def measure_schedule(fn, chunks: Sequence, depth: int, reps: int = 2) -> float:
    """Wall-clock seconds to serve ``chunks`` through executor ``fn`` with
    at most ``depth`` dispatches in flight — the same bounded-pipeline
    dispatch loop the server's drain runs, minus the locking.  Minimum
    over ``reps`` (noise floor, not noise mean)."""
    import jax

    jax.block_until_ready(fn(chunks[0]))  # warm (compile outside timing)
    best = math.inf
    for _ in range(max(int(reps), 1)):
        inflight = deque()
        t0 = time.perf_counter()
        for chunk in chunks:
            if len(inflight) >= depth:
                jax.block_until_ready(inflight.popleft())
            inflight.append(fn(chunk))
        while inflight:
            jax.block_until_ready(inflight.popleft())
        best = min(best, time.perf_counter() - t0)
    return best


def _preference(c: Candidate, plan: SRPlan, batch: int) -> tuple:
    """Tie-break rank among measured near-equals: shallower pipeline,
    then the default band, then the pow2 bucket — simplest schedule wins
    what measurement cannot separate."""
    return (
        c.pipeline_depth,
        0 if c.band_rows == derive_band_rows(plan.height) else 1,
        0 if c.bucket == _pow2_bucket(batch) else 1,
    )


def tune(
    layers: Sequence,
    plan: SRPlan,
    batch: int,
    dtype=np.float32,
    *,
    db: Optional[TuningDB] = None,
    depths: Sequence[int] = DEPTHS,
    max_band_candidates: int = 4,
    prune_ratio: float = 1.5,
    chunks: int = 3,
    reps: int = 2,
    peaks: Optional[RooflinePeaks] = None,
    measure_all: bool = False,
    tie_tol: float = TIE_TOL,
    seed: int = 0,
) -> TuningEntry:
    """Sweep the legal schedule space for ``(plan, batch)``; return — and
    persist, when ``db`` is given — the measured-best schedule.

    ``plan`` is the DEFAULT-derived plan for the configuration (what
    ``SRPlan.from_request`` builds with no tuner).  The sweep enumerates
    candidates, prunes on the analytic roofline at ``prune_ratio`` (the
    default candidate is exempt — the baseline must always be measured),
    compiles each surviving (band_rows, bucket) ONCE over a shared
    :class:`~repro.engine.executor.PreparedStack`, measures every
    surviving depth with :func:`measure_schedule` on a ``chunks``-dispatch
    synthetic clip, and picks the minimum (ties within ``tie_tol`` go to
    the simpler schedule).  ``measure_all=True`` skips pruning — the
    pruning-safety test uses it to check the roofline never discards the
    measured best.
    """
    import jax
    import jax.numpy as jnp

    from repro.engine.executor import build_stack_executor, prepare_stack

    batch = int(batch)
    if batch < 1:
        raise ValueError(f"batch={batch} must be >= 1")
    cands = enumerate_candidates(
        plan, batch, depths=depths, max_band_candidates=max_band_candidates
    )

    # --- analytic pass: score every candidate, prune the hopeless -------
    pred_cache: Dict[Tuple[int, int], float] = {}
    for c in cands:
        pk = (c.band_rows, c.bucket)
        if pk not in pred_cache:
            p = dataclasses.replace(plan, band_rows=c.band_rows)
            pred_cache[pk] = predict_cost(p, layers, c.bucket, batch,
                                          peaks)["ms_per_frame"]
        c.predicted_ms = pred_cache[pk]
    best_pred = min(c.predicted_ms for c in cands)
    if not measure_all:
        for c in cands:
            if not c.is_default and c.predicted_ms > prune_ratio * best_pred:
                c.pruned = True
    survivors = [c for c in cands if not c.pruned]

    # --- measured pass: one compile per (band, bucket), one stack total -
    stack = prepare_stack(plan, layers)  # numerics/packing: band-invariant
    jax.block_until_ready(stack)
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    frames_cache: Dict[int, list] = {}
    fn_cache: Dict[Tuple[int, int], object] = {}
    for c in survivors:
        fk = (c.band_rows, c.bucket)
        if fk not in fn_cache:
            p = dataclasses.replace(plan, band_rows=c.band_rows)
            # own executor, never donated (chunks are reused across reps),
            # never entered into any PlanCache
            fn_cache[fk] = build_stack_executor(p, stack,
                                                donate_frames=False)
        if c.bucket not in frames_cache:
            frames_cache[c.bucket] = [
                jnp.asarray(rng.random(
                    (c.bucket, *plan.lr_shape), np.float32).astype(dtype))
                for _ in range(max(int(chunks), 1))
            ]
        t = measure_schedule(fn_cache[fk], frames_cache[c.bucket],
                             c.pipeline_depth, reps=reps)
        c.measured_ms = t * 1e3 / (len(frames_cache[c.bucket]) * batch)

    best_ms = min(c.measured_ms for c in survivors)
    default = next(c for c in survivors if c.is_default)
    # ties within tie_tol of the best go to the simpler schedule — but a
    # tie-broken winner must never measure WORSE than the default (the
    # tuned >= default guarantee is exact, not within-noise)
    contenders = [c for c in survivors
                  if c.measured_ms <= best_ms * (1 + tie_tol)
                  and c.measured_ms <= default.measured_ms] or [default]
    winner = min(contenders, key=lambda c: _preference(c, plan, batch))

    entry = TuningEntry(
        band_rows=winner.band_rows,
        pipeline_depth=winner.pipeline_depth,
        bucket=winner.bucket,
        bucket_policy="exact" if winner.bucket == batch != _pow2_bucket(batch)
                      else "pow2",
        predicted_ms=round(winner.predicted_ms, 6),
        measured_ms=round(winner.measured_ms, 6),
        default_ms=round(default.measured_ms, 6),
        speedup=round(default.measured_ms / max(winner.measured_ms, 1e-12), 4),
        jax_backend=jax.default_backend(),
        device_kind=device_kind(),
        created=time.time(),
        # tune() measures the single-device executor: entries are only
        # valid for an unsharded consumer on this exact device count
        device_count=jax.device_count(),
        mesh_shape="1x1",
    )
    if db is not None:
        db.put(TuningKey.from_plan(plan, batch), entry)
        db.save()
    # expose the sweep for reporting/tests without widening the return
    entry.candidates = cands  # type: ignore[attr-defined]
    return entry


# ----------------------------------------------------------------------
# The serving-side consumer
# ----------------------------------------------------------------------
class PlanTuner:
    """The serving stack's view of the tuning DB.

    ``SRPlan.from_request(..., tuner=)`` and ``SRSession`` consult it;
    it answers from the DB only (never measures — measurement is
    :func:`tune`, invoked by ``autotune="full"`` sessions or the offline
    ``--sweep``).  Every answer is vetted for numerics safety and
    legality: a ``band_rows`` override must divide the height and must
    only move on a ``halo`` plan; anything else is ignored as stale.
    """

    def __init__(self, db: Optional[TuningDB] = None,
                 path: Optional[str] = None, *,
                 device_count: Optional[int] = None,
                 mesh_shape: str = "1x1"):
        self.db = db if db is not None else TuningDB(path)
        # the consumer's topology: lookups only accept entries stamped
        # with it (a sharded session never adopts 1-device winners)
        self.device_count = device_count
        self.mesh_shape = mesh_shape

    def lookup(
        self, key: TuningKey
    ) -> Tuple[Optional[TuningEntry], str]:
        """``(entry, kind)`` where kind is ``"hit"`` (exact batch),
        ``"fallback"`` (same config, nearest tuned batch) or ``"miss"``."""
        topo = {"device_count": self.device_count,
                "mesh_shape": self.mesh_shape}
        entry = self.db.get(key, **topo)
        if entry is not None and self._safe(key, entry):
            return entry, "hit"
        near = self.db.get_nearest_batch(key, **topo)
        if near is not None and self._safe(key, near[0]):
            return near[0], "fallback"
        return None, "miss"

    def _safe(self, key: TuningKey, entry: TuningEntry) -> bool:
        if key.height % entry.band_rows != 0:
            return False  # stale geometry
        if entry.band_rows != derive_band_rows(key.height):
            # moving band_rows off the default is only numerics-safe
            # under halo (see band_rows_is_tunable)
            return key.vertical_policy == "halo"
        return True

    def band_rows_for(
        self,
        *,
        lr_shape: Tuple[int, int, int],
        num_layers: int,
        tile_cols: int = 8,
        vertical_policy: str = "zero",
        backend: str = "tilted",
        precision: str = "fp32",
        scale: int = 3,
        clip: bool = True,
        bucket: Optional[int] = None,
    ) -> Optional[int]:
        """The measured-best ``band_rows`` for a request configuration, or
        None (fall back to the default derivation).  This is the hook
        ``SRPlan.from_request(..., tuner=)`` calls."""
        H, W, C = (int(x) for x in lr_shape)
        key = TuningKey(
            backend=backend, precision=precision,
            vertical_policy=vertical_policy, height=H, width=W, channels=C,
            num_layers=int(num_layers), tile_cols=int(tile_cols),
            scale=int(scale), clip=bool(clip),
            batch=int(bucket) if bucket else 1,
        )
        entry, _ = self.lookup(key)
        return entry.band_rows if entry is not None else None


# ----------------------------------------------------------------------
# Offline pre-warm CLI
# ----------------------------------------------------------------------
def sweep(
    *,
    db: TuningDB,
    model: str = "abpn_x3",
    backends: Sequence[str] = ("tilted",),
    precisions: Sequence[str] = ("fp32",),
    policies: Sequence[str] = ("zero",),
    heights: Sequence[int] = (120,),
    widths: Sequence[int] = (64,),
    batches: Sequence[int] = (1, 3, 4, 8),
    seed: int = 0,
    **tune_kwargs,
) -> List[Tuple[TuningKey, TuningEntry]]:
    """Tune every configuration in the cross product and persist the
    winners — the offline DB pre-warm behind ``--sweep``."""
    import jax

    from repro.models.registry import get_sr_model

    spec = get_sr_model(model)
    layers = spec.init(jax.random.PRNGKey(seed))
    out = []
    for backend in backends:
        for precision in precisions:
            for policy in policies:
                for h in heights:
                    for w in widths:
                        plan = SRPlan.from_request(
                            (h, w, spec.config.in_channels),
                            num_layers=len(layers),
                            vertical_policy=policy,
                            backend=backend,
                            precision=precision,
                            scale=spec.config.scale,
                        )
                        for b in batches:
                            entry = tune(layers, plan, b, db=db,
                                         **tune_kwargs)
                            key = TuningKey.from_plan(plan, b)
                            out.append((key, entry))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Pre-warm the plan tuning DB offline "
                    "(python -m repro.engine.autotune --sweep)"
    )
    ap.add_argument("--sweep", action="store_true",
                    help="run the tuning sweep and persist winners")
    ap.add_argument("--db", default=None,
                    help=f"tuning DB path (default: ${DB_ENV_VAR} or "
                         "~/.cache/repro-sr/tuning.json)")
    ap.add_argument("--model", default="abpn_x3")
    ap.add_argument("--backends", nargs="+", default=["tilted"],
                    choices=["reference", "tilted", "kernel"])
    ap.add_argument("--precisions", nargs="+", default=["fp32"],
                    choices=["fp32", "bf16", "int8"])
    ap.add_argument("--policies", nargs="+", default=["zero"],
                    choices=["zero", "halo", "replicate"])
    ap.add_argument("--heights", type=int, nargs="+", default=[120])
    ap.add_argument("--widths", type=int, nargs="+", default=[64])
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 3, 4, 8])
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes + shallow grid (CI smoke)")
    args = ap.parse_args(argv)

    if not args.sweep:
        ap.error("nothing to do: pass --sweep to run the tuning sweep")
    db = TuningDB(args.db)
    kw = dict(backends=args.backends, precisions=args.precisions,
              policies=args.policies, heights=args.heights,
              widths=args.widths, batches=args.batches,
              reps=args.reps, chunks=args.chunks)
    if args.quick:
        kw.update(heights=[24], widths=[16], batches=[1, 3],
                  reps=1, chunks=2)
    results = sweep(db=db, model=args.model, **kw)
    for key, e in results:
        print(f"{key.encode()}: band_rows={e.band_rows} "
              f"depth={e.pipeline_depth} bucket={e.bucket} "
              f"({e.bucket_policy}) measured {e.measured_ms:.2f} ms/frame "
              f"(default {e.default_ms:.2f}, x{e.speedup:.3f})")
    print(f"wrote {len(results)} entries -> {db.path}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
