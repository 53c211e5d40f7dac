"""Quickstart: tilted layer fusion in three executors.

Runs the paper's ABPN x3 super-resolution model over a synthetic image via
(1) the plain layer-by-layer reference, (2) the pure-JAX tilted fusion
scan, and (3) the Pallas TPU kernel (interpret mode on CPU), then prints
the equivalence deltas and the modeled buffer/bandwidth numbers that the
paper's Tables I/II report.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.core.analysis import buffer_sizes, dram_reduction, pe_throughput_model
from repro.data.synthetic import sr_pair_batch
from repro.models.abpn import ABPNConfig, init_abpn


def main():
    cfg = ABPNConfig()
    layers = init_abpn(jax.random.PRNGKey(0), cfg)
    lr, _ = sr_pair_batch(0, 1, lr_shape=(120, 64), scale=3)
    print(f"LR {lr.shape[1:]} -> HR x{cfg.scale}")

    # One plan per backend; each runs the (here: single-frame) batch in one
    # jitted engine call.
    def plan(backend, policy="zero"):
        return engine.make_plan(layers, lr.shape[1:], backend=backend,
                                vertical_policy=policy, scale=cfg.scale)

    ref = engine.run(plan("reference"), layers, lr)[0]
    tilted = engine.run(plan("tilted", "halo"), layers, lr)[0]
    kernel = engine.run(plan("kernel"), layers, lr)[0]
    print(f"reference vs tilted(halo): max|d| = "
          f"{np.abs(np.asarray(ref) - np.asarray(tilted)).max():.2e}  (exact)")
    print(f"reference vs Pallas kernel: max|d| = "
          f"{np.abs(np.asarray(ref) - np.asarray(kernel)).max():.2e}  "
          f"(band-boundary rows only)")

    # Shape/batch-agnostic serving: the same weights behind an SRSession —
    # any request shape, plans derived + compiled on demand into the cache.
    session = engine.SRSession.open("abpn_x3", layers=layers, backend="tilted")
    session.upscale(lr)            # (T, H, W, C) clip
    session.upscale(lr[0, :60])    # a single half-height frame, new plan
    c = session.cache_stats()
    print(f"SRSession: {c['misses']} compiles, {c['hits']} hits for "
          f"{[tuple(e['lr_shape'][:2]) for e in c['entries']]}")

    b = buffer_sizes()
    print(f"\non-chip buffers: {b['total_kb']:.2f} KB (paper: 102.36 KB)")
    print(f"DRAM bandwidth reduction: {dram_reduction()*100:.1f}% (paper: 92%)")
    pe = pe_throughput_model()
    print(f"throughput model: {pe['mpix_s_at_target']:.1f} Mpix/s @ "
          f"{pe['utilization']*100:.0f}% MAC utilisation (paper: 124.4 @ 87%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
