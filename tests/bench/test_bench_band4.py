"""The four-chip 4K cell's configuration and its two per-layer readers,
each read from a small hand-built context."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402
from bench.harness import Context  # noqa: E402

BENCH = spec.load_benchmark()
CELL = "abpn_x3_4k.live_60fps_band4"


def ctx(batches=1, trace=None, **stats):
    return Context(stats={"batches": batches, **stats}, trace=trace)


def test_4k_config_is_the_1080p_one_at_720p_on_four_chips():
    four = spec.config(BENCH, "abpn_x3_4k")
    base = spec.config(BENCH, "abpn_x3_1080p")
    assert four["lr_shape"] == [720, 1280, 3]
    assert four["server"] == {**base["server"], "mesh": [1, 4]}
    assert four["reference"] == base["reference"] == "reference"
    same = {"model", "conv_channels", "layer_list", "scale", "matmul_operands", "control"}
    assert {k: four[k] for k in same} == {k: base[k] for k in same}
    # the model's paper is the 1080p configuration's source; this deployment names its own
    assert four["model_source"] == base["source"] != four["source"]
    # its own limits, from its own readings on the chip (PERF.md, section 2)
    assert four["check"] == {"max_gap": 5e-3, "frame_mean_gap": base["check"]["frame_mean_gap"]}
    assert (720 // four["server"]["band_rows"]) % 4 == 0  # whole bands per chip


def test_cell_takes_four_chips_and_the_frame_metrics():
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("abpn_x3_4k", "live_60fps", 4)
    e2e = {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "frame_p50_ms", "frame_p95_ms"}
    layer = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    assert layer == {"shard_put_ms.band4", "chip_busy_spread.band4"}


@pytest.mark.parametrize("c, want", [
    (ctx(shard_put_p50_ms=0.8125), 0.8125),
    (ctx(), None),  # a server without the span (no key)
    (ctx(batches=0, shard_put_p50_ms=0.0), None),
], ids=["mesh_session", "no_span", "no_batch"])
def test_shard_put_reader(c, want):
    assert spec.metric_reader("shard_put_ms.band4")(c) == want


@pytest.mark.parametrize("c, want", [
    (ctx(trace={"busy_s_per_chip": [1.0, 1.1, 0.9, 1.0]}), 20.0),
    (ctx(trace={"busy_s_per_chip": [0.5, 0.5, 0.5, 0.5]}), 0.0),
    (ctx(trace=None), None),
    (ctx(batches=0, trace={"busy_s_per_chip": [1.0, 2.0]}), None),
    (ctx(trace={"busy_s_per_chip": [0.3]}), None),
    (ctx(trace={"busy_s_per_chip": [0.0, 0.0, 0.0, 0.0]}), None),
    (ctx(trace={}), None),
], ids=["spread", "even", "no_trace", "no_batch", "one_chip", "idle", "no_key"])
def test_chip_busy_spread_reader(c, want):
    got = spec.metric_reader("chip_busy_spread.band4")(c)
    assert got == pytest.approx(want) if want is not None else got is None


def test_readers_only_in_the_new_cell():
    for name in ("shard_put_ms.band4", "chip_busy_spread.band4"):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "frame_p50_ms"
