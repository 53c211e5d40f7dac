"""One run of one cell: set-up, the measured window, the metrics, the check.

Set-up (``setup_s``) runs from the first line of ``bench/run.py`` to the
opening of the window: JAX start, weights in one jitted call from the
seed, the seeded LR frame pool, ``SRServer.open``, one pass over
every request shape the mix can reach (the compiles, or the persistent
compile cache's hits), then a full collection and ``gc.freeze()``.  The
window then drives ``SRServer.submit``; nothing in it compiles.
After it: device memory is read, the sampled HR frames go to the host,
the server is closed and its state freed, and only then the reference
runs for the check.
"""

from __future__ import annotations

import gc
import os
import sys
import tempfile
import threading
import time
from typing import Optional

import numpy as np

from bench import check, load, spec, trace_reduce, work

TRACE_S = 2.0  # traced slice at the end of a --trace 1 window
TRACE_SETTLE_S = 0.25  # left out after the profiler starts, before the slice
CACHE_DIR = spec.ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chip(jax, chips: int) -> list:
    """The devices, or exit non-zero: no TPU, too few chips, or a kind
    ``bench/peaks.json`` does not list."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found {len(devices)}")
    try:
        work.peaks(devices[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"bench: {e}") from None
    return devices


def use_compile_cache(jax, path) -> None:
    """JAX's persistent cache at a fixed path in the checkout, every program."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compiles and compile-cache reads while installed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_event)


class WindowHooks(load.Hooks):
    """Counters reset at the window's opening, read at its close; the
    profiler trace of its last slice, with the scheduler's counters where
    the slice begins."""

    def __init__(self, server, session, compiles: CompileCounter, trace_dir):
        self.server, self.session, self.compiles = server, session, compiles
        self.trace_dir = trace_dir
        self.t_trace = self.t_trace_request = self.sched_trace = None
        self.start_trace_s = None
        self._tracer: Optional[threading.Thread] = None

    def window_started(self) -> None:
        self.t_start = time.monotonic()
        self.session.reset_stats()
        self.sched0 = self.server.scheduler_stats()
        self.compiles0 = self.compiles.count

    def trace_from(self, t: float) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        # the Python tracer (on by default) slows every host call
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        self.t_trace_request = t

        def start():
            load.sleep_until(t)
            t0 = time.monotonic()
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.start_trace_s = time.monotonic() - t0
            time.sleep(TRACE_SETTLE_S)
            self.t_trace = time.monotonic()
            self.sched_trace = self.server.scheduler_stats()
            with TraceAnnotation(trace_reduce.BEGIN):
                pass

        self._tracer = threading.Thread(target=start, name="bench-tracer")
        self._tracer.start()

    def window_ended(self) -> None:
        from jax.profiler import TraceAnnotation

        if self._tracer is not None:
            self._tracer.join()
        with TraceAnnotation(trace_reduce.END):
            pass
        self.t_end = time.monotonic()
        self.sched1 = self.server.scheduler_stats()
        self.stats = dict(self.session.stats())
        self.compiles_in_window = self.compiles.count - self.compiles0


class Context:
    """What a metric reader reads (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def latencies_ms(self, lo: Optional[float] = None, hi: Optional[float] = None) -> np.ndarray:
        """Completion minus scheduled arrival of every request due in the
        window (or due in ``[lo, hi)``).  A request that failed or never
        came is charged the whole wait, window close plus
        ``load.GRACE_S``: at least that."""
        w = self.window
        out = []
        for r in w.due():
            if (lo is not None and r.due < lo) or (hi is not None and r.due >= hi):
                continue
            done = r.done if (r.error is None and r.done is not None) else w.end + load.GRACE_S
            out.append((done - r.due) * 1e3)
        return np.asarray(out, np.float64)


def server_options(cfg: dict, mix: dict, overrides: Optional[dict]) -> dict:
    """``SRServer.open`` keywords: the configuration's ``server`` keys, the
    mix's over them, then the overrides (the control's); lists as tuples."""
    opts = {**cfg["server"], **mix.get("server", {}), **(overrides or {})}
    return {k: tuple(v) if isinstance(v, list) else v for k, v in opts.items()}


def open_server(cfg: dict, options: dict, weights, tmp: str):
    from repro.core.fusion import ConvLayer
    from repro.engine import SRServer

    layers = [ConvLayer(w=w, b=b, relu=i < len(weights) - 1)
              for i, (w, b) in enumerate(weights)]
    return SRServer.open(cfg["model"], layers=layers,
                         tuning_db=os.path.join(tmp, "tuning.json"), **options)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None,
             chip: bool = True, overrides: Optional[dict] = None,
             cache_dir=CACHE_DIR, trace_dir: Optional[str] = None) -> dict:
    """One run; returns the result line's object.  ``chip=False`` skips the
    look for a TPU (tests on the CPU); ``overrides`` replace server keys
    (the control: the configuration's ``control``); ``trace_dir`` keeps
    the profiler trace there instead of in a temporary directory."""
    import jax

    bench = bench or spec.load_benchmark()
    wl = spec.workload(bench, cell)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    chips = wl["chips"]
    devices = require_chip(jax, chips) if chip else jax.devices()
    if cache_dir is not None:
        use_compile_cache(jax, cache_dir)
    log(f"device: platform={devices[0].platform} kind={devices[0].device_kind} "
        f"count={len(devices)}; cell {cell}: config {wl['config']} traffic "
        f"{wl['traffic']} chips {chips} seed {seed} seconds {seconds} trace {int(trace)}")
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        return _run(cell, seed, seconds, trace, t_start, bench, cfg, mix,
                    server_options(cfg, mix, overrides), chips, devices, tmp,
                    trace_dir or os.path.join(tmp, "trace"))


def _run(cell, seed, seconds, trace, t_start, bench, cfg, mix, options, chips,
         devices, tmp, trace_dir) -> dict:
    import jax

    ref = spec.reference_module(cfg)
    gen = load.loop(mix["loop"])
    marks = [("jax", time.monotonic())]
    weights = ref.init_weights(work.channels(cfg), seed)
    jax.block_until_ready(weights)
    marks.append(("weights", time.monotonic()))
    pools = load.pools(mix, cfg, seed)
    marks.append(("frames", time.monotonic()))
    compiles = CompileCounter()
    server = open_server(cfg, options, weights, tmp)
    session = server.session()
    for shape in pools:
        plan = session.plan_for(shape)
        if (plan.band_rows, plan.vertical_policy) != (options["band_rows"],
                                                      options["vertical_policy"]):
            raise RuntimeError(f"plan for {shape} serves band_rows={plan.band_rows} "
                               f"{plan.vertical_policy}, not the configuration's")
    marks.append(("server", time.monotonic()))
    warmed = gen.warm(server, pools, mix, options["max_bucket"])
    marks.append(("warm", time.monotonic()))
    # set-up's objects (JAX, the program, its compiled executors) go to the
    # permanent generation, as a serving process does once warm: a full
    # collection in the window then walks what the window made, not them
    gc.collect()
    gc.freeze()
    marks.append(("gc_freeze", time.monotonic()))
    log(f"plan: backend={plan.backend} precision={plan.precision} "
        f"band_rows={plan.band_rows} policy={plan.vertical_policy} "
        f"bands={plan.num_bands}; warmed (shape, bucket) {warmed}; compile events "
        f"in set-up {compiles.count}; executors "
        f"{[(e['bucket'], round(e['compile_s'], 3)) for e in session.cache_stats()['entries']]}")

    hooks = WindowHooks(server, session, compiles, trace_dir)
    window = gen.run(server, pools, mix, seconds, seed, hooks,
                     TRACE_S + TRACE_SETTLE_S if trace else None, tuple(cfg["lr_shape"]))
    gc.unfreeze()  # the program's state is freed for the check below
    setup_s = hooks.t_start - t_start
    log("set-up s: " + _phases(t_start, marks, hooks.t_start))
    ctx = Context(cell=cell, cfg=cfg, mix=mix, seconds=seconds, chips=chips,
                  device_kind=devices[0].device_kind, setup_s=setup_s,
                  window=window, sched0=hooks.sched0, sched1=hooks.sched1,
                  stats=hooks.stats, trace=None, t_trace=hooks.t_trace,
                  t_end=hooks.t_end)
    if trace:
        jax.profiler.stop_trace()
        loaded = trace_reduce.load(trace_reduce.find_xplane(hooks.trace_dir))
        ctx.trace = trace_reduce.reduce(loaded, chips)
        log(f"trace: start_trace took {hooks.start_trace_s!r} s; window_s="
            f"{ctx.trace['window_s']!r} busy_s per chip {ctx.trace['busy_s_per_chip']} "
            f"ops={ctx.trace['ops']} device lines {loaded['device_lines']} "
            f"host spans {len(loaded['host'])}")
        _log_slice(ctx, hooks)
    compiles.close()
    used = devices[:chips]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    if window.send_lag_s:
        lag = np.asarray(window.send_lag_s) * 1e3
        log(f"generator send lag ms: max={float(lag.max())!r} "
            f"p95={float(np.percentile(lag, 95))!r} over {lag.size} sends")
    log(f"compile events in window: {hooks.compiles_in_window}")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, cell, kind):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted, failed_reqs = gen.counts(window)
    failed = sum(r.frames for r in failed_reqs)
    refusals = set(mix.get("refusals", []))
    wrongly_failed = [r for r in failed_reqs
                      if r.error is None or type(r.error).__name__ not in refusals]
    errors = {}
    for r in failed_reqs:
        key = f"{type(r.error).__name__}: {str(r.error)[:300]}" if r.error else "never done"
        errors[key] = errors.get(key, 0) + r.frames
    for key, frames in errors.items():
        log(f"failed frames: {frames} with {key}")

    sampled = [r for r in window.requests if r.sample and r.error is None]
    lr, hr = {}, {}
    for r in sampled:
        for i, a in r.kept:
            a = np.asarray(a, np.float32)
            a = a.reshape(-1, *a.shape[-3:])
            lr.setdefault(r.shape, []).append(pools[r.shape][i:i + len(a)])
            hr.setdefault(r.shape, []).append(a)
    missing = sum(1 for r in sampled if not r.kept)
    for r in window.requests:
        r.kept = []
    reduced = ctx.trace
    server.close()
    session.clear_cache()
    del server, session, ctx, window
    gc.collect()

    t_check = time.monotonic()
    if hr:
        numbers = check.gaps(cfg, options, weights,
                             {s: (np.concatenate(lr[s]), np.concatenate(hr[s])) for s in hr})
    else:
        numbers = {name: float("inf") for name in cfg["check"]}
    table = check.verdict(cfg, numbers)
    correct = check.passes(table) and not wrongly_failed and missing == 0 and bool(hr)
    log(f"check: {sum(len(h) for v in hr.values() for h in v)} frames of "
        f"{len(sampled)} sampled requests ({missing} without output) against "
        f"bench/{cfg['reference']}.py in {time.monotonic() - t_check!r} s; "
        f"attempted={attempted} failed={failed} (not refusals: "
        f"{sum(r.frames for r in wrongly_failed)}) correct={correct}")
    for name, v in table.items():
        log(f"check {name}={v['value']!r} limit={v['limit']!r}")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = table
    return result


def _log_slice(ctx: Context, hooks: WindowHooks) -> None:
    """The traced slice against the window before it, so that a profiler
    that changes what the cell runs shows: frames per dispatch and the
    median latency of each."""
    s0, st, s1 = hooks.sched0, hooks.sched_trace, hooks.sched1

    def per_dispatch(a, b):
        d = b["dispatches"] - a["dispatches"]
        return (b["frames_dispatched"] - a["frames_dispatched"]) / d if d else float("nan")

    before = ctx.latencies_ms(hi=hooks.t_trace_request)
    during = ctx.latencies_ms(lo=hooks.t_trace)
    p50 = lambda a: float(np.percentile(a, 50)) if a.size else float("nan")  # noqa: E731
    log(f"traced slice: frames per dispatch {per_dispatch(st, s1)!r} (before it "
        f"{per_dispatch(s0, st)!r}); p50 ms {p50(during)!r} over {during.size} "
        f"requests (before it {p50(before)!r} over {before.size})")


def _phases(t_start: float, marks: list, t_window: float) -> str:
    out, prev = [], t_start
    for name, t in marks + [("window_opens", t_window)]:
        out.append(f"{name}={t - prev!r}")
        prev = t
    return " ".join(out)
