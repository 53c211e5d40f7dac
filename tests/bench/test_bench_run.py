"""The harness end to end on the CPU at a tiny size, the look for a chip
skipped: a sound run is correct; the control and each fault a cell can
have (an answer altered where it is produced, half a batch left out, one
shard's rows left out) are not.  The tiny configurations state float32
operands, which is what the CPU computes; the limits are the cells' own.
An offline cell (the closed loop) and a band-sharded 4-device cell are
defined here: the benchmark has neither yet (see PERF.md)."""

import copy
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402
from bench.harness import run_cell  # noqa: E402
import repro.engine.executor as executor  # noqa: E402

TINY = {"lr_shape": [24, 32, 3], "matmul_operands": "float32"}
OFFLINE, LIVE, BAND4 = ("abpn_x3_1080p.offline_clips", "abpn_x3_1080p.live_60fps",
                        "abpn_x3_4k.live_60fps_band4")
BURSTY, MIXED = "abpn_x3_1080p.live_bursty", "abpn_x3_1080p.mixed_res"
# Mixes the benchmark has no cell for yet, each data alone: the offline
# clips, streams joining in a burst under shedding and deadlines, and two
# resolutions at once.
MIXES = {
    "offline_clips": {"loop": "closed", "clients": 2, "clip_frames": 16, "pool_frames": 32,
                      "check_requests": 2, "check_positions": [0, 5, 10, 15],
                      "check_from": 4, "check_span": 24},
    "live_bursty": {"loop": "open", "pool_frames": 8, "check_frames": 8,
                    "groups": [{"streams": 1, "fps": 60},
                               {"streams": 6, "fps": 60, "start_s": 0.2, "stop_s": 0.4,
                                "submit": {"timeout": 0.05}}],
                    "server": {"admission": "shed", "max_inflight_frames": 4},
                    "refusals": ["RequestShedError", "DeadlineExceededError",
                                 "QueueFullError"]},
    "mixed_res": {"loop": "open", "pool_frames": 8, "check_frames": 16,
                  "groups": [{"streams": 2, "fps": 60},
                             {"streams": 1, "fps": 30, "lr_shape": [36, 32, 3]}]},
}
SEED = 2**32 + 5


def tiny_bench(tmp_dir) -> dict:
    bench = copy.deepcopy(spec.load_benchmark())
    cfg = spec.config(bench, "abpn_x3_1080p")
    cfg = {**cfg, **TINY, "server": {**cfg["server"], "band_rows": 12}}
    four = {**cfg, "lr_shape": [48, 32, 3], "server": {**cfg["server"], "mesh": [1, 4]}}
    bench["configs"] = []
    for name, c in (("abpn_x3_1080p", cfg), ("abpn_x3_4k", four)):
        path = os.path.join(tmp_dir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(c, f)
        bench["configs"].append({"name": name, "file": path})
    bench["workloads"] += [
        {"name": OFFLINE, "config": "abpn_x3_1080p", "traffic": "offline_clips", "chips": 1},
        {"name": BURSTY, "config": "abpn_x3_1080p", "traffic": "live_bursty", "chips": 1},
        {"name": MIXED, "config": "abpn_x3_1080p", "traffic": "mixed_res", "chips": 1},
        {"name": BAND4, "config": "abpn_x3_4k", "traffic": "live_60fps", "chips": 4}]
    return bench


@pytest.fixture(autouse=True)
def test_mixes(monkeypatch):
    sound = spec.traffic
    monkeypatch.setattr(spec, "traffic", lambda name: MIXES.get(name) or sound(name))


def run(bench, cell, **kw):
    kw.setdefault("trace", False)
    return run_cell(cell, SEED, 0.5, kw.pop("trace"), t_start=time.monotonic(),
                    bench=bench, chip=False, cache_dir=None, **kw)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", [OFFLINE, LIVE, BURSTY, MIXED])
def test_sound_run_is_correct(bench, cell):
    r = run(bench, cell)
    assert r["correct"] and r["attempted"] > 0
    assert r["failed"] == 0 or cell == BURSTY  # the burst is shed, as the mix allows
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert all(m["value"] > 0 for m in r["metrics"].values())
    want = {m["name"] for m in spec.metrics_for(bench, cell, "end_to_end")}
    assert set(r["metrics"]) == want
    assert r["device"]["count"] == len(jax.devices())


@pytest.mark.parametrize("cell", [LIVE, MIXED])
def test_nothing_compiles_in_the_window(bench, cell, capsys):
    """Set-up warms every program the window runs, the sampled outputs'
    handling too: with JAX's caches cleared first, the window compiles
    nothing."""
    jax.clear_caches()
    assert run(bench, cell)["correct"]
    assert "compile events in window: 0\n" in capsys.readouterr().err


def test_control_is_not_correct(bench):
    cfg = spec.config(bench, "abpn_x3_1080p")
    r = run(bench, OFFLINE, overrides=cfg["control"])
    assert not r["correct"]
    assert any(v["value"] > v["limit"] for v in r["check"].values())


@pytest.mark.parametrize("cell", [OFFLINE, LIVE, MIXED])
def test_altered_answer_is_not_correct(bench, cell, monkeypatch):
    sound = executor._execute_stack

    def altered(plan, stack, frames):
        return sound(plan, stack, frames).at[:, 0, 0, 0].add(0.25)

    monkeypatch.setattr(executor, "_execute_stack", altered)
    r = run(bench, cell)
    assert not r["correct"] and r["check"]["max_gap"]["value"] > 0.2


def test_half_the_batch_left_out_is_not_correct(bench, monkeypatch):
    sound = executor._execute_stack

    def half(plan, stack, frames):
        k = max(1, frames.shape[0] // 2)
        hr = sound(plan, stack, frames[:k])
        return jnp.concatenate([hr, jnp.zeros((frames.shape[0] - k, *hr.shape[1:]), hr.dtype)])

    monkeypatch.setattr(executor, "_execute_stack", half)
    r = run(bench, OFFLINE)
    assert not r["correct"] and r["check"]["frame_mean_gap"]["value"] > 0.1


def test_traced_run_reduces_the_trace(bench):
    r = run(bench, LIVE, trace=True)
    assert r["correct"]
    assert "host_dispatch_ms.live" in r["metrics"]
    assert set(r["metrics"]) <= {m["name"] for m in spec.metrics_for(bench, LIVE, "per_layer")}
    assert r["device"]["window_s"] > 0 and "breakdown" in r
    assert list(r)[-1] == "check"


def test_altered_answer_of_one_resolution_is_not_correct(bench, monkeypatch):
    sound = executor._execute_stack

    def altered(plan, stack, frames):
        hr = sound(plan, stack, frames)
        return hr.at[:, 0, 0, 0].add(0.25) if frames.shape[1] == 36 else hr

    monkeypatch.setattr(executor, "_execute_stack", altered)
    r = run(bench, MIXED)
    assert not r["correct"] and r["check"]["max_gap"]["value"] > 0.2


def test_unlisted_failure_is_not_correct(bench, monkeypatch):
    """Refusals the mix names are failures, not faults; any other error is."""
    mixes = {**MIXES, "live_bursty": {**MIXES["live_bursty"], "refusals": []}}
    monkeypatch.setattr(spec, "traffic", lambda name: mixes[name])
    from repro.engine import SRServer

    sound = SRServer.submit

    def shed_every_third(self, frames, **kw):
        shed_every_third.n += 1  # the warm-up's 1 + 2 + 4 submits pass
        if shed_every_third.n > 7 and shed_every_third.n % 3 == 0:
            raise RuntimeError("refused")
        return sound(self, frames, **kw)

    shed_every_third.n = 0
    monkeypatch.setattr(SRServer, "submit", shed_every_third)
    r = run(bench, BURSTY)
    assert not r["correct"] and r["failed"] > 0


def test_server_options_layering():
    cfg = {"server": {"precision": "fp32", "band_rows": 60, "mesh": [1, 4]}}
    mix = {"server": {"admission": "shed", "band_rows": 30}}
    from bench.harness import server_options

    assert server_options(cfg, mix, {"precision": "bf16"}) == {
        "precision": "bf16", "band_rows": 30, "mesh": (1, 4), "admission": "shed"}


SHARDED = """
import json, os, sys, time
sys.path.insert(0, {root!r})
sys.path.insert(0, {test_dir!r})
import jax, jax.numpy as jnp
from test_bench_run import tiny_bench, run, BAND4
from bench import spec
import repro.engine.sharding.shard_exec as se
from repro.launch.mesh import SR_BAND_AXIS

bench = tiny_bench({tmp!r})
out = {{"sound": run(bench, BAND4)["correct"],
        "control": run(bench, BAND4, overrides=spec.config(bench, "abpn_x3_4k")["control"])["correct"]}}
sound = se._sharded_body

def dropped(splan, stack, frames):
    hr = sound(splan, stack, frames)
    return jnp.where(jax.lax.axis_index(SR_BAND_AXIS) == 1, jnp.zeros_like(hr), hr)

se._sharded_body = dropped
out["shard_dropped"] = run(bench, BAND4)["correct"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    code = SHARDED.format(root=ROOT, test_dir=os.path.dirname(os.path.abspath(__file__)),
                          tmp=str(tmp_path_factory.mktemp("tiny4")))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case, correct", [("sound", True), ("control", False),
                                           ("shard_dropped", False)])
def test_band_sharded_cell(sharded, case, correct):
    assert sharded[case] is correct


def _run_py(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, capture_output=True,
                          text=True, timeout=300, env=env)


def test_no_chip_exits_nonzero_without_result():
    r = _run_py(["bench/run.py", "--workload", LIVE, "--seed", str(SEED),
                 "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil

    for p in spec.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.BENCHMARK_JSON, tmp_path / "BENCHMARK.json")
    r = _run_py(["bench/run.py", "--workload", LIVE, "--seed", "1", "--seconds", "1",
                 "--trace", "0"], str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""
