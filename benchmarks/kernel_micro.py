"""Kernel microbenchmarks.

Wall-clock here is CPU interpret-mode (correctness vehicle, not TPU perf);
the ``derived`` column therefore reports the MODELED TPU numbers from the
dry-run machinery: per-tile MXU FLOPs, VMEM working set claimed by the
BlockSpecs, and the analytic HBM traffic of the streaming layout.

    PYTHONPATH=src python benchmarks/kernel_micro.py    # CSV rows

Run standalone by CI's bench-smoke job (the Pallas datapath must at least
execute + produce its modeled numbers on every change); also exposes
``rows()`` for the ``benchmarks/run.py`` harness.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.analysis import HWConfig
from repro.kernels import ops
from repro.models.abpn import ABPNConfig, init_abpn


def _time(fn, *args, iters=3):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def rows():
    cfg = ABPNConfig()
    hw = HWConfig()
    layers = init_abpn(jax.random.PRNGKey(0), cfg)
    img = jax.random.uniform(jax.random.PRNGKey(1), (60, 64, 3))

    us_fused = _time(
        lambda x: ops.tilted_fused_stack(x, layers, band_rows=60, tile_cols=8),
        img,
    )
    w = layers[1].w
    b = layers[1].b
    feat = jax.random.uniform(jax.random.PRNGKey(2), (60, 64, 28))
    us_conv = _time(lambda x: ops.conv3x3(x, w, b), feat)

    # modeled TPU numbers per (8-col x 60-row) tile, chp=32 padding
    chp, C, R, L = 32, 8, 60, 7
    tile_flops = L * 9 * 2 * (R * C) * chp * chp
    vmem_kb = (
        (R * C * chp)  # out block
        + (R * C * 8)  # in block (c0p=8)
        + L * 9 * chp * chp  # weights
        + L * R * 2 * chp  # overlap scratch
        + R * (C + L) * 8  # residual ring
    ) * 4 / 1e3
    return [
        ("kernel.tilted_fused_stack", us_fused,
         f"interpret-mode; modeled {tile_flops/1e6:.2f} MFLOP/tile on MXU"),
        ("kernel.conv3x3", us_conv,
         f"interpret-mode; vectorwise layer datapath"),
        ("kernel.vmem_claim_kb", 0.0,
         f"{vmem_kb:.0f} KB f32 VMEM/tile (SRAM analogue: {102.36} KB int8)"),
    ]


def main() -> int:
    print("name,us_per_call,derived")
    for name, us, derived in rows():
        print(f'{name},{us:.1f},"{derived}"')
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys

    sys.exit(main())
