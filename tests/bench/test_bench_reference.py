"""bench/reference.py against the program's own conv stack and epilogue at a
tiny size on the CPU (the reference imports nothing of the program; the
test may)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import load, reference  # noqa: E402
from repro.core.fusion import ConvLayer, conv_stack_reference  # noqa: E402
from repro.engine.executor import sr_epilogue  # noqa: E402
from repro.engine.plan import SRPlan  # noqa: E402

CH = (3, 28, 28, 28, 28, 28, 27, 27)  # ABPN x3 as published
STACKS = {"published": CH, "repo_registry": (3, 28, 28, 28, 28, 28, 28, 27)}


def _program_hr(weights, lr, band_rows):
    layers = [ConvLayer(w=w, b=b, relu=i < len(weights) - 1)
              for i, (w, b) in enumerate(weights)]
    n, h, w, c = lr.shape
    with jax.default_matmul_precision("highest"):
        bands = lr.reshape(n * h // band_rows, band_rows, w, c)
        feats = jax.vmap(lambda b: conv_stack_reference(b, layers))(bands)
        feats = feats.reshape(n, h, w, -1)
        plan = SRPlan(height=h, width=w, in_channels=c, num_layers=len(layers),
                      band_rows=band_rows, vertical_policy="zero", scale=3)
        return sr_epilogue(plan, lr, feats, lr.dtype)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_matches_program_stack_and_epilogue(stack):
    weights = reference.init_weights(STACKS[stack], 2**31 + 3)
    lr = jnp.asarray(load.frame_pool((24, 16, 3), 2, 5))
    want = np.asarray(_program_hr(weights, lr, 12))
    got = np.asarray(reference.hr_frames(weights, lr, scale=3, band_rows=12,
                                         policy="zero", operands="float32"))
    assert got.shape == (2, 72, 48, 3)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_zero_bands_differ_from_whole_frame():
    weights = reference.init_weights(CH, 11)
    lr = jnp.asarray(load.frame_pool((24, 16, 3), 1, 6))
    kw = dict(scale=3, policy="zero", operands="float32")
    zero = np.asarray(reference.hr_frames(weights, lr, band_rows=12, **kw))
    full = np.asarray(reference.hr_frames(weights, lr, band_rows=24, **kw))
    assert np.abs(zero - full).max() > 1e-3  # band edges see zero rows
    np.testing.assert_array_equal(zero[:, :3 * 5], full[:, :3 * 5])  # far from the edge


def test_other_vertical_policies_are_refused():
    weights = reference.init_weights(CH, 13)
    lr = jnp.zeros((1, 24, 16, 3), jnp.float32)
    with pytest.raises(ValueError, match="vertical policy"):
        reference.hr_frames(weights, lr, scale=3, band_rows=12, policy="halo",
                            operands="float32")


def test_bf16_operands_round_each_layer_input():
    weights = reference.init_weights(CH, 12)
    lr = jnp.asarray(load.frame_pool((12, 16, 3), 1, 7))
    kw = dict(scale=3, band_rows=12, policy="zero")
    exact = np.asarray(reference.hr_frames(weights, lr, operands="float32", **kw))
    rounded = np.asarray(reference.hr_frames(weights, lr, operands="bfloat16", **kw))
    gap = np.abs(exact - rounded)
    assert 1e-4 < gap.mean() < 5e-3 and gap.max() < 5e-2


def test_weights_are_the_seeds_and_fp32():
    a = reference.init_weights(CH, 2**33 + 1)
    b = reference.init_weights(CH, 2**33 + 1)
    c = reference.init_weights(CH, 2**33 + 2)
    assert [w.shape for w, _ in a] == [(3, 3, ci, co) for ci, co in zip(CH, CH[1:])]
    assert all(w.dtype == jnp.float32 for w, _ in a)
    assert all(np.array_equal(x, y) for (x, _), (y, _) in zip(a, b))
    assert not np.array_equal(a[0][0], c[0][0])


def test_frame_pool_is_seeded_float32_in_unit_range():
    a = load.frame_pool((24, 32, 3), 3, 9)
    assert a.dtype == np.float32 and a.shape == (3, 24, 32, 3)
    assert 0.0 <= a.min() and a.max() <= 1.0
    np.testing.assert_array_equal(a, load.frame_pool((24, 32, 3), 3, 9))
    assert not np.array_equal(a, load.frame_pool((24, 32, 3), 3, 10))
