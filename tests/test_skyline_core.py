"""block-SFS and the skyline buffers vs the O(N^2) oracle, including
hypothesis property tests over distributions, duplicates, and masks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import block_sfs, compact, naive_skyline_mask, skyline
from repro.core.datagen import generate


def _as_set(pts, mask):
    return set(map(tuple, np.asarray(pts)[np.asarray(mask)]))


@pytest.mark.parametrize("dist", ["uniform", "correlated", "anticorrelated"])
@pytest.mark.parametrize("n,d", [(100, 2), (500, 4), (257, 7)])
def test_block_sfs_matches_oracle(dist, n, d):
    pts = generate(dist, jax.random.PRNGKey(n + d), n, d)
    want = _as_set(pts, naive_skyline_mask(pts))
    sky = block_sfs(pts, capacity=n, block=64)
    assert _as_set(sky.points, sky.mask) == want
    assert int(sky.count) == len(want)
    assert not bool(sky.overflow)


def test_block_sfs_respects_mask():
    pts = jnp.array([[0.0, 0.0], [0.5, 0.5], [0.6, 0.4]], jnp.float32)
    mask = jnp.array([False, True, True])  # exclude the dominator
    sky = block_sfs(pts, mask, capacity=4, block=2)
    assert _as_set(sky.points, sky.mask) == _as_set(pts, mask)


def test_duplicates_all_kept():
    # equal tuples do not dominate each other (strict < required)
    pts = jnp.array([[0.3, 0.7]] * 5 + [[0.8, 0.9]], jnp.float32)
    sky = block_sfs(pts, capacity=8, block=4)
    assert int(sky.count) == 5
    mask = naive_skyline_mask(pts)
    assert int(mask.sum()) == 5


def test_overflow_flag_and_subset_guarantee():
    pts = generate("anticorrelated", jax.random.PRNGKey(0), 400, 5)
    full = block_sfs(pts, capacity=400, block=64)
    small_cap = max(int(full.count) // 3, 1)
    sky = block_sfs(pts, capacity=small_cap, block=64)
    assert bool(sky.overflow)
    # never a spurious member: result is a subset of the true skyline
    assert _as_set(sky.points, sky.mask) <= _as_set(full.points, full.mask)


def test_skyline_empty_input_returns_wellformed_buffer():
    """Regression: n == 0 used to derive capacity=0 and push a zero-row
    window through block_sfs; it must return an empty SkyBuffer."""
    pts = jnp.zeros((0, 3), jnp.float32)
    buf = skyline(pts)
    assert buf.points.shape[1] == 3
    assert buf.points.shape[0] >= 1
    assert int(buf.count) == 0
    assert not bool(buf.overflow)
    assert not bool(buf.mask.any())


def test_skyline_all_masked_input():
    pts = generate("uniform", jax.random.PRNGKey(1), 32, 4)
    buf = skyline(pts, jnp.zeros((32,), jnp.bool_))
    assert int(buf.count) == 0
    assert not bool(buf.overflow)
    assert not bool(buf.mask.any())


def test_compact():
    pts = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    mask = jnp.array([True, False, True, False, True, False])
    buf = compact(pts, mask, 4)
    assert int(buf.count) == 3
    got = np.asarray(buf.points)[np.asarray(buf.mask)]
    np.testing.assert_array_equal(got, np.asarray(pts)[::2])


@pytest.mark.parametrize("n,d", [(1, 2), (50, 2), (300, 4), (257, 7)])
def test_canonical_order_is_lexsort(n, d):
    """The chained single-key sorts give exactly the multi-key lexsort
    (score first, then coordinates, then input position) — with score
    ties, duplicate rows, -0.0 and invalid (+inf score) rows."""
    from repro.core.dominance import canonical_order, monotone_score
    rng = np.random.default_rng(n * 10 + d)
    pts = (rng.integers(0, 3, (n, d)) / 2).astype(np.float32)
    pts[rng.random(n) < 0.1, 0] = -0.0
    mask = rng.random(n) > 0.2
    score = np.asarray(monotone_score(jnp.asarray(pts), jnp.asarray(mask)))
    want = np.lexsort(tuple(pts[:, j] for j in reversed(range(d)))
                      + (score,))
    got = np.asarray(jax.jit(canonical_order)(jnp.asarray(pts),
                                              jnp.asarray(mask)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,cap,frac", [(1, 1, 0.5), (7, 3, 0.5),
                                        (100, 100, 0.0), (100, 40, 1.0),
                                        (1000, 257, 0.3), (64, 128, 0.7)])
def test_compact_order_is_stable_partition(n, cap, frac):
    """The sort-free compaction order is exactly a stable argsort of
    ~mask, truncated — what every caller's side columns rely on."""
    from repro.core.sfs import compact_order
    rng = np.random.default_rng(n + cap)
    mask = rng.random(n) < frac
    want = np.argsort(~mask, kind="stable")[:cap]
    got = np.asarray(jax.jit(compact_order, static_argnums=1)(
        jnp.asarray(mask), cap))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 200), st.integers(2, 7), st.integers(0, 3),
       st.integers(0, 2 ** 31 - 1))
def test_hypothesis_sfs_oracle(n, d, quant, seed):
    """Random data with heavy ties (quantized) across sizes/dims."""
    rng = np.random.default_rng(seed)
    levels = [3, 5, 17, 0][quant]
    if levels:
        pts = jnp.asarray(rng.integers(0, levels, (n, d)) / levels,
                          jnp.float32)
    else:
        pts = jnp.asarray(rng.random((n, d)), jnp.float32)
    mask = jnp.asarray(rng.random(n) > 0.2)
    want = _as_set(pts, naive_skyline_mask(pts, mask))
    sky = block_sfs(pts, mask, capacity=n, block=32)
    assert _as_set(sky.points, sky.mask) == want
    assert not bool(sky.overflow)


def test_skyline_api():
    pts = generate("uniform", jax.random.PRNGKey(7), 300, 3)
    sky = skyline(pts)
    want = _as_set(pts, naive_skyline_mask(pts))
    assert _as_set(sky.points, sky.mask) == want
