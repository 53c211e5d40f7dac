"""Plain jax.numpy ABPN (Du et al., arXiv:2105.09750): the yardstick.

Weights from the seed, then per frame: a chain of 3x3 convs over the
configuration's ``conv_channels`` with ReLU on all but the last, the
anchor (each LR channel repeated scale**2 times) added, depth-to-space,
clip to [0, 1].  Under the ``zero`` vertical policy, the only one a
configuration states so far, every ``band_rows``-row band is an image of
its own, zero-padded at its edges.  Convolutions run at HIGHEST matmul
precision on operands first rounded to ``matmul_operands``: ``bfloat16``
states the arithmetic of XLA's DEFAULT precision on the TPU (bf16
operands, products and sums in fp32).  Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The last layer's weights are scaled down so that the residual, as in a
# trained ABPN, stays small against the anchor: with plain He weights a
# third of the HR values clip to 0 or 1 and compare equal whatever the
# arithmetic.
LAST_LAYER_GAIN = 0.2
BIAS_STD = 0.01


def prng_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, however wide."""
    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(1)[0]))


@functools.partial(jax.jit, static_argnums=1)
def _init(key: jax.Array, ch: tuple) -> list:
    # one normal draw for every weight and bias, cut into layers: a small
    # program to trace and compile, however many layers there are
    sizes = [n for ci, co in zip(ch, ch[1:]) for n in (9 * ci * co, co)]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    weights, at = [], 0
    for i, (ci, co) in enumerate(zip(ch, ch[1:])):
        gain = LAST_LAYER_GAIN if i == len(ch) - 2 else 1.0
        w = flat[at:at + 9 * ci * co].reshape(3, 3, ci, co) * (gain * (2.0 / (9 * ci)) ** 0.5)
        at += 9 * ci * co
        weights.append((w, flat[at:at + co] * BIAS_STD))
        at += co
    return weights


def init_weights(ch, seed: int) -> list:
    """``[(w (3, 3, Ci, Co), b (Co,)), ...]`` in fp32 on the device, He-normal
    weights (the last layer's scaled by ``LAST_LAYER_GAIN``) and small
    normal biases, in one jitted call."""
    return _init(prng_key(seed), tuple(ch))


def _features(weights, image: jax.Array, operands) -> jax.Array:
    f = image
    for i, (w, b) in enumerate(weights):
        f = jax.lax.conv_general_dilated(
            f.astype(operands).astype(jnp.float32)[None],
            w.astype(operands).astype(jnp.float32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)[0] + b
        if i < len(weights) - 1:
            f = jax.nn.relu(f)
    return f


def depth_to_space(x: jax.Array, s: int) -> jax.Array:
    """(H, W, C*s*s) -> (H*s, W*s, C); out[y*s+dy, x*s+dx, c] = in[y, x, c*s*s + dy*s + dx]."""
    h, w, cs = x.shape
    c = cs // (s * s)
    return x.reshape(h, w, c, s, s).transpose(0, 3, 1, 4, 2).reshape(h * s, w * s, c)


@functools.partial(jax.jit, static_argnames=("scale", "band_rows", "policy", "operands"))
def hr_frames(weights, lr: jax.Array, *, scale: int, band_rows: int,
              policy: str, operands: str) -> jax.Array:
    """LR ``(N, H, W, C)`` fp32 -> HR ``(N, H*s, W*s, C)``."""
    if policy != "zero":
        raise ValueError(f"reference has no vertical policy {policy!r}")
    n, h, w, c = lr.shape
    bands = lr.reshape(n * h // band_rows, band_rows, w, c)
    feats = jax.vmap(lambda im: _features(weights, im, jnp.dtype(operands)))(bands)
    out = feats.reshape(n, h, w, -1) + jnp.repeat(lr, scale * scale, axis=-1)
    hr = jax.vmap(lambda o: depth_to_space(o, scale))(out)
    return jnp.clip(hr, 0.0, 1.0)
