"""Representative Filtering (paper §4.1), Grid Filtering (§3.2) and NoSeq
(§4.2, Proposition 2) — soundness and exactness properties."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import example, given, settings, st

from repro.core import naive_skyline_mask
from repro.core.datagen import generate
from repro.core.dominance import region_volume
from repro.core.filtering import (filter_by_representatives, grid_filter,
                                  select_representatives)
from repro.core.parallel import SkyConfig, parallel_skyline


def _sky_set(pts, mask=None):
    return set(map(tuple, np.asarray(pts)[np.asarray(
        naive_skyline_mask(pts, mask))]))


@pytest.mark.parametrize("strategy", ["sorted", "region", "random"])
def test_representative_filtering_is_sound(strategy):
    """Filtering never deletes a skyline member (only dominated tuples)."""
    pts = generate("uniform", jax.random.PRNGKey(0), 400, 4)
    mask = jnp.ones(400, bool)
    reps, rmask = select_representatives(
        pts, mask, 16, strategy=strategy, key=jax.random.PRNGKey(1))
    new_mask = filter_by_representatives(pts, mask, reps, rmask)
    sky = naive_skyline_mask(pts)
    assert not np.asarray(sky & ~new_mask).any()
    # representatives are pairwise non-dominated after dedup
    from repro.kernels.dominance import dominated_mask_ref
    dom = dominated_mask_ref(reps, reps, rmask)
    assert not np.asarray(dom & rmask).any()


def test_sorted_reps_filter_more_than_random_on_average():
    drops = {}
    for strategy in ["sorted", "random"]:
        total = 0
        for seed in range(3):
            pts = generate("uniform", jax.random.PRNGKey(seed), 600, 4)
            mask = jnp.ones(600, bool)
            reps, rmask = select_representatives(
                pts, mask, 8, strategy=strategy,
                key=jax.random.PRNGKey(seed + 10))
            total += int((~filter_by_representatives(
                pts, mask, reps, rmask)).sum())
        drops[strategy] = total
    assert drops["sorted"] > drops["random"]


def test_region_volume():
    pts = jnp.array([[0.0, 0.0], [0.5, 0.5], [1.0, 1.0]], jnp.float32)
    np.testing.assert_allclose(np.asarray(region_volume(pts)),
                               [1.0, 0.25, 0.0])


def test_grid_filter_sound_and_effective():
    pts = generate("uniform", jax.random.PRNGKey(3), 2000, 4)
    mask = jnp.ones(2000, bool)
    gf = grid_filter(pts, mask, m=4)
    # soundness: no skyline member dropped
    sky = naive_skyline_mask(pts)
    assert not np.asarray(sky & ~gf.mask).any()
    # effectiveness: on uniform data a 4^4 grid filters a large share
    assert int(gf.dropped) > 500


def test_grid_filter_distribution_ordering():
    """Paper §5.1: correlated ~90% > uniform ~58% > anticorrelated ~16%."""
    frac = {}
    for dist in ["uniform", "correlated", "anticorrelated"]:
        pts = generate(dist, jax.random.PRNGKey(4), 3000, 4)
        gf = grid_filter(pts, jnp.ones(3000, bool), m=4)
        frac[dist] = float(gf.dropped) / 3000.0
    assert frac["correlated"] > frac["uniform"] > frac["anticorrelated"]


@pytest.mark.parametrize("strategy", ["random", "sliced", "grid", "angular"])
@pytest.mark.parametrize("dist", ["uniform", "anticorrelated"])
def test_proposition2_noseq_identity(strategy, dist):
    pts = generate(dist, jax.random.PRNGKey(5), 600, 4)
    cfg = SkyConfig(strategy=strategy, p=8, capacity=1024, block=64,
                    bucket_factor=8.0, noseq=True)
    buf, stats = parallel_skyline(pts, cfg=cfg)
    assert not bool(buf.overflow), stats
    got = set(map(tuple, np.asarray(buf.points)[np.asarray(buf.mask)]))
    assert got == _sky_set(pts)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(["random", "sliced", "grid", "angular"]),
       st.sampled_from([None, "sorted", "region"]),
       st.booleans(), st.integers(0, 2 ** 31 - 1))
# equal sliced values straddle a slice boundary: a later slice holds the
# only dominator of a row
@example(strategy="sliced", rep=None, noseq=True, seed=1)
def test_hypothesis_full_pipeline(strategy, rep, noseq, seed):
    """Prop 1 + Prop 2 + rep-filtering composed, random quantized data."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(30, 300))
    d = int(rng.integers(2, 6))
    pts = jnp.asarray(rng.integers(0, 8, (n, d)) / 8.0, jnp.float32)
    cfg = SkyConfig(strategy=strategy, p=4, capacity=max(n, 16), block=32,
                    bucket_factor=float(n), rep_filter=rep, rep_k=4,
                    noseq=noseq)
    buf, _ = parallel_skyline(pts, cfg=cfg)
    assert not bool(buf.overflow)
    got = set(map(tuple, np.asarray(buf.points)[np.asarray(buf.mask)]))
    assert got == _sky_set(pts)


# Slices of 2 rows (n = 8, p = 4).  x and y share the sliced value 0.5;
# the sort breaks the tie by row index, so x (row 3) ends slice 1 and y
# (row 4) opens slice 2, and y is x's only dominator.
TIE_ROWS = [[0.1, 0.95], [0.2, 0.9], [0.3, 0.85], [0.5, 0.6],
            [0.5, 0.4], [0.6, 0.3], [0.7, 0.2], [0.8, 0.1]]
TIE_CASE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.parallel import SkyConfig, parallel_skyline
from repro.launch.mesh import make_worker_mesh
pts = jnp.asarray({rows}, jnp.float32)
want = {{tuple(r) for r in np.asarray(pts)[[0, 1, 2, 4, 5, 6, 7]]}}
base = SkyConfig(strategy="sliced", p=4, capacity=16, block=8,
                 bucket_factor=2.0, noseq=True)
meshes = [make_worker_mesh({devices})] + ([None] if {devices} == 1 else [])
for mesh in meshes:
    for merge in ("flat", "tree"):
        cfg = dataclasses.replace(base, merge=merge)
        buf, st = parallel_skyline(pts, cfg=cfg, mesh=mesh)
        got = {{tuple(r) for r in np.asarray(buf.points)[
            np.asarray(buf.mask)]}}
        assert got == want and int(buf.count) == 7, (merge, mesh, got)
        assert not bool(buf.overflow)
print("OK")
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_noseq_sliced_dominator_in_a_later_slice(devices):
    """NoSeq over SLICED finds a dominator that a tie put in the next
    slice, under the flat and the tree merge, on one forced host device
    (with and without a workers mesh) and on a four-worker mesh."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = TIE_CASE.format(rows=TIE_ROWS, devices=devices)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
