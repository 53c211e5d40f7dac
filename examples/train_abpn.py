"""End-to-end driver: train the paper's ABPN model on synthetic SR pairs.

A few hundred steps on CPU; PSNR vs the nearest-neighbour anchor baseline
is printed every 25 steps.  (--steps 300 default; the paper's model is
43K params, so this trains in minutes.)

    PYTHONPATH=src python examples/train_abpn.py --steps 300
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import sr_pair_batch
from repro.models.abpn import ABPNConfig, apply_abpn, init_abpn, make_anchor, depth_to_space


def psnr(a, b):
    mse = float(jnp.mean((a - b) ** 2))
    return 10 * np.log10(1.0 / max(mse, 1e-12))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--size", type=int, default=24)
    args = ap.parse_args()

    cfg = ABPNConfig()
    layers = init_abpn(jax.random.PRNGKey(0), cfg)

    def loss_fn(layers, lr_b, hr_b):
        out = jax.vmap(lambda im: apply_abpn(layers, im, cfg))(lr_b)
        return jnp.mean(jnp.abs(out - hr_b))

    @jax.jit
    def step(layers, lr_b, hr_b):
        l, g = jax.value_and_grad(loss_fn)(layers, lr_b, hr_b)
        return jax.tree_util.tree_map(lambda p, gg: p - args.lr * gg, layers, g), l

    val_lr, val_hr = sr_pair_batch(10_000, 8, lr_shape=(args.size, args.size))
    anchor_up = jax.vmap(lambda im: depth_to_space(make_anchor(im, 3), 3))(val_lr)
    print(f"anchor (nearest-neighbour) baseline PSNR: {psnr(anchor_up, val_hr):.2f} dB")

    t0 = time.time()
    for i in range(args.steps):
        lr_b, hr_b = sr_pair_batch(i, args.batch, lr_shape=(args.size, args.size))
        layers, l = step(layers, lr_b, hr_b)
        if i % 25 == 0 or i == args.steps - 1:
            out = jax.vmap(lambda im: apply_abpn(layers, im, cfg))(val_lr)
            print(f"step {i:4d}  loss {float(l):.4f}  val PSNR {psnr(out, val_hr):.2f} dB"
                  f"  ({(time.time()-t0)/(i+1):.2f}s/step)")
    print("done — the model beats its anchor whenever PSNR exceeds the baseline")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
