"""The `ant5` deployment (`bench/configs/ant5.json`: anti-correlated
data, SLICED+ and the NoSeq merge) cut to 4,096 rows on the CPU, with
the Pallas kernels in interpret mode.

Its answers equal the plain O(n^2) skyline and pass the benchmark's own
check (`bench/reference.check`); its `noseq_pd_refs` counter equals a
NumPy count over the local skylines; and its program names NoSeq's
phase-2 test `noseq_mask`, which a program without NoSeq does not hold.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import naive_skyline_mask, parallel
from repro.core.parallel import SkyConfig, parallel_skyline
from repro.launch.mesh import make_worker_mesh
from test_stage_scopes import program_kernels

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import datagen, reference  # noqa: E402

with open(os.path.join(ROOT, "bench", "configs", "ant5.json")) as f:
    CONF = json.load(f)
N = 4096
# the capacities cut with the table (buckets of 513 rows; about 1.4e3
# skyline rows at this size)
CFG = SkyConfig(**dict(CONF["sky_config"], local_capacity=512,
                       capacity=4096, impl="interpret"))


def _table(seed: int):
    return datagen.anticorrelated(jax.random.PRNGKey(seed), N, CONF["d"])


def _pd_refs_numpy(cfg: SkyConfig, pts, key) -> int:
    """Valid rows of the other local skylines that are potential
    dominators of each slice, summed over the slices: every row of an
    earlier slice, and a row of a later one whose sliced value is at
    most the slice's largest."""
    buckets, _, _ = parallel.partition_stage(pts, None, cfg, key)
    sky, _ = parallel.local_stage(buckets.points, buckets.mask, cfg,
                                  key=jax.random.fold_in(key, 1))
    mask = np.asarray(sky.mask)
    vals = np.asarray(sky.points)[..., cfg.sliced_dim]
    top = np.where(mask, vals, -np.inf).max(axis=1)
    total = 0
    for i in range(len(top)):
        for j in range(len(top)):
            if j < i:
                total += mask[j].sum()
            elif j > i:
                total += (mask[j] & (vals[j] <= top[i])).sum()
    return int(total)


@pytest.mark.parametrize("merge", ["flat", "tree"])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_ant5_cut_matches_the_references(merge, seed):
    pts = _table(seed % 2 ** 32)
    key = jax.random.PRNGKey(7)
    cfg = dataclasses.replace(CFG, merge=merge)
    # the tree merge needs a workers axis: one forced host device
    mesh = make_worker_mesh(1) if merge == "tree" else None
    buf, stats = parallel_skyline(pts, cfg=cfg, key=key, mesh=mesh)
    assert not bool(buf.overflow)
    assert not bool(stats["bucket_overflow"])
    assert not bool(stats["local_overflow"])
    got = np.asarray(buf.points)[np.asarray(buf.mask)]
    want = np.asarray(pts)[np.asarray(naive_skyline_mask(pts))]
    assert len(got) == int(buf.count) == len(want)
    assert set(map(tuple, got)) == set(map(tuple, want))
    assert reference.check(pts, got) == (0, 0)
    assert int(stats["noseq_pd_refs"]) == _pd_refs_numpy(cfg, pts, key)


@pytest.mark.parametrize("noseq", [True, False])
def test_ant5_program_names_the_noseq_test(noseq):
    kernels = program_kernels(dataclasses.replace(CFG, noseq=noseq), N,
                              CONF["d"])
    assert ("noseq_mask" in kernels) == noseq
    assert "dominated_mask" in kernels and "sfs_sweep" in kernels
