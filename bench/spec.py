"""BENCHMARK.json and the files it names, each found by name."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_benchmark(path: Path = BENCHMARK_JSON) -> dict:
    with open(path) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _by_name(bench["configs"], name, "config")
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list the cell under ``workloads``, or list no cells at all."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """``read(ctx) -> float | None`` from ``bench/metrics/<name>.py``."""
    module = load_module(BENCH_DIR / "metrics" / f"{name}.py",
                         f"bench_metric_{name.replace('.', '_')}")
    return module.read


def reference_module(cfg: dict):
    """The configuration's plain reference, ``bench/<reference>.py``."""
    return load_module(BENCH_DIR / f"{cfg['reference']}.py",
                       f"bench_reference_{cfg['reference']}")
