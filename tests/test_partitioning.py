"""Partitioning strategies: Proposition 1 (partition-local-merge identity),
bucketize integrity, balance properties, grid/angular index validity."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import naive_skyline_mask
from repro.core.datagen import generate
from repro.core.dominance import SENTINEL
from repro.core.parallel import SkyConfig, effective_parts, parallel_skyline
from repro.core.partition import (angular_part_ids, bucketize,
                                  grid_cell_coords, grid_part_ids,
                                  random_part_ids, sliced_part_ids)

STRATEGIES = ["random", "sliced", "grid", "angular"]


def _sky_set(pts, mask=None):
    return set(map(tuple, np.asarray(pts)[np.asarray(
        naive_skyline_mask(pts, mask))]))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("dist", ["uniform", "anticorrelated"])
def test_proposition1_identity(strategy, dist):
    """SKY(r) == SKY(SKY(r_1) u ... u SKY(r_p)) for every strategy."""
    pts = generate(dist, jax.random.PRNGKey(1), 500, 4)
    cfg = SkyConfig(strategy=strategy, p=8, capacity=1024, block=64,
                    bucket_factor=8.0)
    buf, stats = parallel_skyline(pts, cfg=cfg)
    assert not bool(buf.overflow), stats
    got = set(map(tuple, np.asarray(buf.points)[np.asarray(buf.mask)]))
    assert got == _sky_set(pts)


def test_bucketize_routes_every_valid_tuple_once():
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.random((200, 3)), jnp.float32)
    mask = jnp.asarray(rng.random(200) > 0.3)
    ids = jnp.asarray(rng.integers(0, 7, 200), jnp.int32)
    b = bucketize(pts, mask, ids, 7, capacity=200)
    assert not bool(b.overflow)
    # per-partition contents match
    for p in range(7):
        want = {tuple(r) for r in np.asarray(pts)[
            np.asarray(mask) & (np.asarray(ids) == p)]}
        got = {tuple(r) for r in np.asarray(b.points[p])[
            np.asarray(b.mask[p])]}
        assert got == want
        assert int(b.counts[p]) == len(want)


def test_bucketize_overflow_detection():
    pts = jnp.zeros((50, 2), jnp.float32)
    ids = jnp.zeros((50,), jnp.int32)
    b = bucketize(pts, jnp.ones(50, bool), ids, 4, capacity=10)
    assert bool(b.overflow)
    assert int(b.counts[0]) == 50


def _np_bucketize(pts, mask, ids, p, capacity):
    """Plain routing: each partition's valid rows in input order, the first
    `capacity` of them kept, `counts` the valid rows per id."""
    n, d = pts.shape
    points = np.full((p, capacity, d), SENTINEL, pts.dtype)
    bmask = np.zeros((p, capacity), bool)
    counts = np.zeros((p,), np.int32)
    for j in range(p):
        rows = np.flatnonzero(mask & (ids == j))
        counts[j] = rows.size
        keep = rows[:capacity]
        points[j, :keep.size] = pts[keep]
        bmask[j, :keep.size] = True
    return points, bmask, counts, np.bool_(np.any(counts > capacity))


def _routing_case(name, rng):
    n, d, p, cap = 120, 3, 5, 120
    pts = rng.random((n, d)).astype(np.float32)
    mask = rng.random(n) > 0.25
    ids = rng.integers(0, p, n).astype(np.int32)
    if name == "p1":
        p, ids = 1, np.zeros(n, np.int32)
    elif name == "empty":
        pts, mask, ids = pts[:0], mask[:0], ids[:0]
    elif name == "all_invalid":
        mask = np.zeros(n, bool)
    elif name == "overflow":
        cap = 7
    elif name == "last_partition":
        ids = np.full(n, p - 1, np.int32)
    elif name == "inf_values":
        pts[rng.random((n, d)) < 0.2] = np.inf
    return pts, mask, ids, p, cap


def _assert_same_buckets(got, want):
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("case", ["p1", "empty", "all_invalid", "overflow",
                                  "last_partition", "inf_values", "vmap"])
def test_bucketize_matches_numpy_routing(case):
    rng = np.random.default_rng(7)
    if case == "vmap":
        # the batch programs route each query's table under jax.vmap
        cases = [_routing_case(case, rng) for _ in range(3)]
        pts, mask, ids = (np.stack([c[k] for c in cases]) for k in range(3))
        p, cap = cases[0][3:]
        route = jax.vmap(lambda x, m, i: bucketize(x, m, i, p, cap))
        want = [np.stack(w) for w in zip(*(_np_bucketize(*c) for c in cases))]
    else:
        pts, mask, ids, p, cap = _routing_case(case, rng)
        route = functools.partial(bucketize, p=p, capacity=cap)
        want = _np_bucketize(pts, mask, ids, p, cap)
    got = route(jnp.asarray(pts), jnp.asarray(mask), jnp.asarray(ids))
    _assert_same_buckets(got, want)
    assert bool(np.any(got.overflow)) == (case == "overflow")


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


def test_bucketize_has_no_scatter_and_no_table_wide_loop():
    """Routing reads the buckets by gathers: no scatter, and its one loop
    (the binary search for each partition's two bounds) gathers 2p entries
    per level, not one per row."""
    n, p, cap = 4096, 5, 1024
    args = (jnp.zeros((n, 3), jnp.float32), jnp.ones((n,), bool),
            jnp.zeros((n,), jnp.int32))
    f = functools.partial(bucketize, p=p, capacity=cap)
    assert "scatter" not in jax.jit(f).lower(*args).as_text()
    eqns = list(_all_eqns(jax.make_jaxpr(f)(*args).jaxpr))
    assert not [e for e in eqns if "scatter" in e.primitive.name]
    loops = [e for e in eqns if e.primitive.name in ("scan", "while")]
    assert len(loops) <= 1
    for loop in loops:
        assert loop.params.get("length", 0) <= int(np.ceil(np.log2(n + 1)))
        body = loop.params.get("jaxpr", loop.params.get("body_jaxpr"))
        gathers = [e for e in _all_eqns(body.jaxpr)
                   if e.primitive.name == "gather"]
        assert gathers
        assert all(np.prod(g.invars[1].aval.shape) == 2 * p for g in gathers)


def test_random_and_sliced_balance():
    n, p = 1000, 8
    ids = random_part_ids(jax.random.PRNGKey(0), n, p)
    counts = np.bincount(np.asarray(ids), minlength=p)
    assert counts.max() - counts.min() <= 1
    pts = generate("uniform", jax.random.PRNGKey(1), n, 3)
    ids = sliced_part_ids(pts, jnp.ones(n, bool), p)
    counts = np.bincount(np.asarray(ids), minlength=p)
    assert counts.max() - counts.min() <= 1


def test_sliced_is_sorted_runs():
    pts = generate("uniform", jax.random.PRNGKey(2), 300, 2)
    ids = np.asarray(sliced_part_ids(pts, jnp.ones(300, bool), 4))
    x = np.asarray(pts[:, 0])
    for lo in range(3):
        assert x[ids == lo].max() <= x[ids == lo + 1].min() + 1e-7


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 300), st.integers(2, 6), st.integers(2, 4),
       st.integers(0, 2 ** 31 - 1))
def test_grid_angular_ids_in_range(n, d, m, seed):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.random((n, d)), jnp.float32)
    gid = np.asarray(grid_part_ids(pts, m))
    assert gid.min() >= 0 and gid.max() < m ** d
    aid = np.asarray(angular_part_ids(pts, m))
    assert aid.min() >= 0 and aid.max() < m ** (d - 1)
    coords = np.asarray(grid_cell_coords(pts, m))
    assert (coords >= 0).all() and (coords < m).all()


def test_grid_dominance_cell_consistency():
    """t dominates s => cell(t) <= cell(s) coordinate-wise."""
    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.random((200, 3)), jnp.float32)
    coords = np.asarray(grid_cell_coords(pts, 4))
    from repro.kernels.dominance import dominance_matrix_ref
    dom = np.asarray(dominance_matrix_ref(pts, pts))
    js, is_ = np.nonzero(dom)
    assert (coords[js] <= coords[is_]).all()


def test_effective_parts():
    cfg = SkyConfig(strategy="grid", p=16)
    assert effective_parts(cfg, 4) == (16, 2)
    cfg = SkyConfig(strategy="angular", p=25)
    assert effective_parts(cfg, 3) == (25, 5)
    cfg = SkyConfig(strategy="sliced", p=12)
    assert effective_parts(cfg, 5) == (12, 0)


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(STRATEGIES), st.integers(20, 250),
       st.integers(2, 5), st.integers(0, 2 ** 31 - 1))
def test_hypothesis_prop1_all_strategies(strategy, n, d, seed):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.integers(0, 10, (n, d)) / 10.0, jnp.float32)
    cfg = SkyConfig(strategy=strategy, p=4, capacity=max(n, 16), block=32,
                    bucket_factor=float(n), rep_filter=None)
    buf, _ = parallel_skyline(pts, cfg=cfg)
    assert not bool(buf.overflow)
    got = set(map(tuple, np.asarray(buf.points)[np.asarray(buf.mask)]))
    assert got == _sky_set(pts)
