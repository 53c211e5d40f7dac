"""BENCHMARK.json against the benchmark contract, and every file it names
loading by name — the layout later cells, mixes and metrics add to."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402

BENCH = spec.load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH_RE.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_names_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for e in BENCH["configs"] + BENCH["workloads"] + METRICS:
        assert spec.NAME_RE.match(e["name"]), e["name"]
    for w in BENCH["workloads"]:
        assert spec.NAME_RE.match(w["config"]) and spec.NAME_RE.match(w["traffic"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_loads(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"]) <= 200
    cfg = spec.config(BENCH, entry["name"])
    assert cfg["source"] == entry["source"]
    assert set(cfg["check"]) == {"max_gap", "frame_mean_gap"}
    assert os.path.isfile(os.path.join(spec.BENCH_DIR, f"{cfg['reference']}.py"))
    assert hasattr(spec.reference_module(cfg), "hr_frames")
    assert {"precision", "vertical_policy", "band_rows", "max_bucket"} <= set(cfg["server"])
    ch = cfg["conv_channels"]
    assert ch[0] == cfg["lr_shape"][2] and ch[-1] == ch[0] * cfg["scale"] ** 2
    for key in entry["reduced"]:
        assert spec.NAME_RE.match(key)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_well_formed(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    mix = spec.traffic(cell["traffic"])
    assert os.path.isfile(spec.BENCH_DIR / "loops" / f"{mix['loop']}.py")
    kinds = {m["name"] for m in spec.metrics_for(BENCH, cell["name"], "end_to_end")}
    assert "setup_s" in kinds and len(kinds) >= 2
    assert spec.metrics_for(BENCH, cell["name"], "per_layer")


def test_four_chip_cells_within_share():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_loads(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in BENCH["end_to_end"]:
        allowed |= {"bound"}
        assert 0 < metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        allowed |= {"layer", "moves"}
        assert 1 <= len(metric["layer"]) <= 200
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
        for cell in metric["workloads"]:
            assert cell in CELLS
            assert cell in moved.get("workloads", [cell])
    assert set(metric) <= allowed
    assert spec.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert callable(spec.metric_reader(metric["name"]))


LOOPS = sorted(f[:-3] for f in os.listdir(spec.BENCH_DIR / "loops") if f.endswith(".py"))


@pytest.mark.parametrize("name", LOOPS)
def test_loop_loads_by_name(name):
    from bench import load

    gen = load.loop(name)
    assert all(callable(getattr(gen, f, None)) for f in ("warm", "run", "counts"))


def test_every_file_is_named_by_the_benchmark():
    """No orphan: each config, traffic mix and metric file belongs to an entry."""
    mixes = {w["traffic"] for w in BENCH["workloads"]}
    assert {f[:-5] for f in os.listdir(spec.BENCH_DIR / "traffic")} == mixes
    files = {e["file"] for e in BENCH["configs"]}
    assert {f"bench/configs/{f}" for f in os.listdir(spec.BENCH_DIR / "configs")} == files
    readers = {f[:-3] for f in os.listdir(spec.BENCH_DIR / "metrics") if f.endswith(".py")}
    assert readers == {m["name"] for m in METRICS}


def test_peaks_table_has_its_source():
    with open(spec.BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["flops_per_s"] == 197e12
