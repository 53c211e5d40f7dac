"""Compile the serving path's TPU programs for a described v5e chip.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what Mosaic or XLA would
refuse on the chip (unaligned blocks, unlowerable ops, programs that do
not fit HBM).  Interpret-mode tests cannot see any of that.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.  The persistent compilation cache stays off around these
compiles — a program compiled for a described chip cannot be read back.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.executor import build_stack_executor, prepare_stack
from repro.engine.plan import SRPlan
from repro.kernels import ops
from repro.kernels.conv3x3 import conv3x3_call
from repro.kernels.tilted_fusion import tilted_fusion_call
from repro.models.abpn import init_abpn

V5E_HBM_BYTES = 16 * 10**9
EPILOGUE_TEMP_BOUND = 1.5 * 10**9  # bucket-4 temporaries, lane-dense epilogue
R, W, C, L, CHP, C0P = 60, 640, 8, 7, 32, 8  # ABPN x3 at 640x360


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no libtpu log files
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("row_bounds", [False, True], ids=["bands", "bounds"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
def test_tilted_fusion_kernel_compiles(one_chip, dtype, row_bounds):
    """One frame's six 60-row bands of a 640-wide frame, anchored."""
    bands = 6

    def launch(xs, first, w, b, *bounds):
        return tilted_fusion_call(
            xs, first, w, b, width=W, tile_cols=C,
            relu_flags=[True] * (L - 1) + [False], add_anchor=True,
            in_channels=3, row_bounds=bounds[0] if bounds else None,
            compute_dtype=dtype, interpret=False)

    args = [_spec(one_chip, (bands, R, W, C0P), dtype),
            _spec(one_chip, (bands, R, 1, C0P), dtype),
            _spec(one_chip, (L, 3, 3, CHP, CHP), dtype),
            _spec(one_chip, (L, CHP), dtype)]
    if row_bounds:
        args.append(_spec(one_chip, (bands, 2), jnp.int32))
    compiled = jax.jit(launch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_conv3x3_kernel_compiles(one_chip):
    def launch(x, w, b):
        return conv3x3_call(x, w, b, tile_cols=C, interpret=False)

    compiled = jax.jit(launch).lower(
        _spec(one_chip, (R, W, CHP)), _spec(one_chip, (3, 3, CHP, CHP)),
        _spec(one_chip, (CHP,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@functools.cache
def _serving_program(sharding, backend, height=360, width=W, bucket=4):
    """The donated serving program (default: batch 4 at 640x360 ->
    1920x1080), compiled for the described chip; callers steer
    ``default_interpret`` off."""
    plan = SRPlan.from_request((height, width, 3), num_layers=L, backend=backend)
    stack = jax.eval_shape(lambda l: prepare_stack(plan, l),
                           init_abpn(jax.random.PRNGKey(0)))
    fn = build_stack_executor(plan, stack, donate_frames=True)
    return fn.jitted.lower(
        plan,
        jax.tree_util.tree_map(lambda a: _spec(sharding, a.shape, a.dtype), stack),
        _spec(sharding, (bucket, height, width, 3)),
    ).compile()


@pytest.mark.parametrize("backend", ["tilted", "kernel"])
def test_serving_executor_fits_v5e(one_chip, backend, monkeypatch):
    """The donated batch-4 serving program at 640x360 -> 1920x1080 fits the
    chip's HBM; the kernel backend's program holds the Mosaic kernel."""
    # default_interpret() sees this host's CPU; the chip never interprets
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    compiled = _serving_program(one_chip, backend)
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "kernel")


@pytest.mark.parametrize("backend", ["tilted", "kernel"])
def test_serving_epilogue_stays_lane_dense(one_chip, backend, monkeypatch):
    """The batch-4 serving program's temporaries stay under 1.5 GB (0.91 GB
    tilted, 0.96 GB kernel).  An epilogue that materialises the pixel
    shuffle with a sub-pixel axis on the 128 lanes pads it 42x and takes
    4.25 GB."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    compiled = _serving_program(one_chip, backend)
    assert compiled.memory_analysis().temp_size_in_bytes < EPILOGUE_TEMP_BOUND


@pytest.mark.parametrize(
    "height,width,bucket,bytes_bound",
    [(360, W, 4, 4.0e9), (180, 2 * W, 1, 2.2e9), (360, W, 1, 3.2e9)],
    ids=["640x360_bucket4", "4k_shard_bucket1", "640x360_bucket1"])
def test_tilted_serving_program_has_no_scan_loop(one_chip, height, width,
                                                 bucket, bytes_bound):
    """The tilted backend sweeps each band in one tile, so its serving
    program holds no ``while`` (an 8-column sweep is an 81-step scan at
    640 columns, 161 at 1280), and its temporaries stay lane-dense.  The
    second shape is one chip's shard of the 4K band-sharded cell: 3 bands
    of 60 rows, 1280 columns.

    A one-tile band is a plain chain of SAME convolutions over its W
    columns: the program holds no column mask of the tilted width
    (``pred[648]``, ``pred[1288]``; 47 at 640x360 bucket 1 when the tilt's
    offsets and masks were kept), and the bytes it accesses stay about 10%
    under each bound: 3.64 GB at 640x360 bucket 4, 2.01 GB on the 4K shard
    at bucket 1, 2.92 GB at 640x360 bucket 1 (4.69, 4.16 and 3.97 GB with
    the tilt's bookkeeping kept)."""
    compiled = _serving_program(one_chip, "tilted", height, width, bucket)
    text = compiled.as_text()
    assert re.search(r"\bwhile\(", text) is None
    assert compiled.memory_analysis().temp_size_in_bytes < EPILOGUE_TEMP_BOUND
    assert re.search(r"pred\[(648|1288)\]", text) is None
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < bytes_bound
