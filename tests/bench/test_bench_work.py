"""bench/work.py against hand counts for ABPN x3 at 360x640 and 720x1280."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec, work  # noqa: E402

BENCH = spec.load_benchmark()
CFG = spec.config(BENCH, "abpn_x3_1080p")
CFG_4K = {**CFG, "lr_shape": [720, 1280, 3]}


def test_channels_are_the_published_widths():
    assert work.channels(CFG) == [3, 28, 28, 28, 28, 28, 27, 27]


def test_flops_per_lr_pixel():
    # 2 x 9 x (3*28 + 4*28*28 + 28*27 + 27*27) = 2 x 9 x 4705
    assert work.flops_per_lr_pixel(work.channels(CFG)) == 84_690


@pytest.mark.parametrize("cfg, gflop", [(CFG, 19.512576), (CFG_4K, 78.050304)])
def test_flops_per_frame(cfg, gflop):
    assert work.flops_per_frame(cfg) == pytest.approx(gflop * 1e9, rel=0, abs=1)


def test_bytes_per_frame_and_weights():
    lr, hr = work.frame_bytes(CFG)
    assert (lr, hr) == (2_764_800, 24_883_200)
    # 4 B x (9 x 4705 weights + 5 x 28 + 2 x 27 biases)
    assert work.weight_bytes(work.channels(CFG)) == 170_156
    assert work.least_bytes(CFG, 4, 1) == 4 * (lr + hr) + 170_156


def test_floor_is_compute_on_v5e():
    peak = work.peaks("TPU v5 lite")
    t_flops = work.flops_per_frame(CFG) / peak["flops_per_s"]
    t_bytes = sum(work.frame_bytes(CFG)) / peak["hbm_bytes_per_s"]
    assert t_flops == pytest.approx(9.905e-5, rel=1e-3)
    assert t_bytes == pytest.approx(3.376e-5, rel=1e-3)


def test_frame_shape_overrides_the_configuration():
    assert work.flops_per_frame(CFG, (720, 1280, 3)) == work.flops_per_frame(CFG_4K)
    assert work.frame_bytes(CFG, (720, 1280, 3)) == work.frame_bytes(CFG_4K)


def test_unlisted_device_kind_is_an_error():
    with pytest.raises(KeyError, match="peaks.json"):
        work.peaks("cpu")
