"""Tilted layer fusion executor vs the plain conv stack (exactness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare env: deterministic fallback sampler
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.fusion import (
    ConvLayer,
    conv_stack_reference,
    run_banded,
    tilted_fused_band,
)
from repro.core.tiling import make_schedule


def make_layers(key, channels, bias_scale=0.1):
    layers = []
    for i in range(len(channels) - 1):
        k1, k2, key = jax.random.split(key, 3)
        ci, co = channels[i], channels[i + 1]
        layers.append(
            ConvLayer(
                w=jax.random.normal(k1, (3, 3, ci, co)) * (2.0 / (9 * ci)) ** 0.5,
                b=jax.random.normal(k2, (co,)) * bias_scale,  # nonzero bias
                relu=(i < len(channels) - 2),                 # catches phantom leaks
            )
        )
    return layers


def test_single_band_bit_exact():
    """The paper's core claim: zero information loss left/right."""
    key = jax.random.PRNGKey(0)
    layers = make_layers(key, [3, 28, 28, 28, 28, 28, 28, 27])
    x = jax.random.uniform(jax.random.PRNGKey(1), (60, 64, 3))
    ref = conv_stack_reference(x, layers)
    til = tilted_fused_band(x, layers, tile_cols=8)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(til))


@pytest.mark.slow
@pytest.mark.parametrize("one_tile", [False, True], ids=["tiles", "one_tile"])
@settings(max_examples=15, deadline=None)
@given(
    width=st.integers(5, 49),
    tile_cols=st.integers(2, 9),
    depth=st.integers(1, 5),
    ch=st.integers(1, 6),
    rows=st.integers(3, 12),
)
def test_band_exactness_property(one_tile, width, tile_cols, depth, ch, rows):
    """Exact against the plain stack at any tile width, including a tile
    that covers the band's whole tilted sweep (tile_cols >= W + L - 1)."""
    if one_tile:
        tile_cols += width + depth - 3  # >= width + depth - 1
        assert make_schedule(width, tile_cols, depth).num_tiles == 1
    key = jax.random.PRNGKey(width * 131 + tile_cols)
    layers = make_layers(key, [2] + [ch] * depth)
    x = jax.random.uniform(jax.random.PRNGKey(3), (rows, width, 2))
    ref = conv_stack_reference(x, layers)
    til = tilted_fused_band(x, layers, tile_cols=tile_cols)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(til), atol=1e-5)


@pytest.mark.parametrize("policy", ["zero", "replicate", "halo"])
def test_one_tile_matches_eight_column_tiles(policy):
    """The default one-tile sweep computes what the 8-column sweep does,
    under every vertical policy."""
    layers = make_layers(jax.random.PRNGKey(11), [3, 8, 8, 8, 5])
    img = jax.random.uniform(jax.random.PRNGKey(12), (60, 44, 3))
    one = run_banded(img, layers, band_rows=30, vertical_policy=policy)
    eight = run_banded(img, layers, band_rows=30, tile_cols=8,
                       vertical_policy=policy)
    np.testing.assert_allclose(np.asarray(one), np.asarray(eight), atol=1e-5)


@pytest.mark.slow
def test_halo_policy_full_image_exact():
    key = jax.random.PRNGKey(5)
    layers = make_layers(key, [3, 8, 8, 5])
    img = jax.random.uniform(jax.random.PRNGKey(6), (90, 40, 3))
    ref = conv_stack_reference(img, layers)
    out = run_banded(img, layers, band_rows=30, tile_cols=4, vertical_policy="halo")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_zero_policy_differs_only_at_band_boundaries():
    key = jax.random.PRNGKey(7)
    L = 4
    layers = make_layers(key, [3] + [6] * L)
    img = jax.random.uniform(jax.random.PRNGKey(8), (90, 40, 3))
    ref = np.asarray(conv_stack_reference(img, layers))
    out = np.asarray(
        run_banded(img, layers, band_rows=30, tile_cols=4, vertical_policy="zero")
    )
    diff = np.abs(ref - out).max(axis=(1, 2))
    # interior rows (further than L from any band boundary) must be exact
    for b0 in (0, 30, 60):
        interior = slice(b0 + L, b0 + 30 - L)
        assert diff[interior].max() == 0.0
    # and something must differ at the boundaries (otherwise no trade-off)
    assert diff.max() > 0


def test_replicate_policy_runs():
    key = jax.random.PRNGKey(9)
    layers = make_layers(key, [3, 4, 4])
    img = jax.random.uniform(jax.random.PRNGKey(10), (20, 16, 3))
    out = run_banded(img, layers, band_rows=10, tile_cols=4,
                     vertical_policy="replicate")
    assert out.shape == (20, 16, 4)
    assert np.isfinite(np.asarray(out)).all()


def test_tile_cols_must_cover_overlap():
    layers = make_layers(jax.random.PRNGKey(0), [3, 4])
    x = jnp.zeros((8, 16, 3))
    with pytest.raises(ValueError):
        tilted_fused_band(x, layers, tile_cols=1)
