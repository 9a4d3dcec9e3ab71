"""Partitioning strategies (paper §3): RANDOM, GRID, ANGULAR, SLICED.

Each strategy maps every tuple to a partition id in [0, p). The SPMD
runtime then routes tuples into fixed-capacity per-partition buckets
(`bucketize`) — the static-shape analogue of Spark's shuffle
(DESIGN.md §3 change (2)).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.dominance import SENTINEL

__all__ = [
    "random_part_ids", "grid_part_ids", "grid_cell_coords",
    "angular_part_ids", "sliced_part_ids", "bucketize", "Buckets",
    "grid_num_parts", "angular_num_parts", "slices_for_target_parts",
]


class Buckets(NamedTuple):
    points: jnp.ndarray    # (p, C, d)
    mask: jnp.ndarray      # (p, C) bool
    counts: jnp.ndarray    # (p,) int32 true per-partition populations
    overflow: jnp.ndarray  # () bool — some partition exceeded capacity


# --------------------------------------------------------------------------
# Partition-id maps
# --------------------------------------------------------------------------

def random_part_ids(key: jax.Array, n: int, p: int) -> jnp.ndarray:
    """Balanced random assignment: a random permutation of residues mod p
    (exactly equi-numerous when p | n, off by one otherwise) — paper §3.1."""
    return jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32) % p)


def grid_cell_coords(pts: jnp.ndarray, m: int) -> jnp.ndarray:
    """(N, d) int32 grid coordinates on [0,1]^d with m slices per dim."""
    return jnp.clip(jnp.floor(pts * m), 0, m - 1).astype(jnp.int32)


def grid_part_ids(pts: jnp.ndarray, m: int) -> jnp.ndarray:
    """p(t) = sum_i floor(t[A_i] * m) * m^(i-1) — paper §3.2."""
    d = pts.shape[1]
    coords = grid_cell_coords(pts, m)
    radix = (m ** jnp.arange(d, dtype=jnp.int32))
    return jnp.sum(coords * radix[None, :], axis=1)


def angular_part_ids(pts: jnp.ndarray, m: int) -> jnp.ndarray:
    """Hyperspherical partitioning (paper §3.3, Eq. 1): grid on the d-1
    angular coordinates; phi_i = arctan(sqrt(sum_{j>i} x_j^2) / x_i)."""
    n, d = pts.shape
    if d < 2:
        return jnp.zeros((n,), jnp.int32)
    x2 = pts.astype(jnp.float32) ** 2
    # tail[i] = sum_{j > i} x_j^2 via reversed cumulative sum
    rev_cum = jnp.cumsum(x2[:, ::-1], axis=1)[:, ::-1]
    tail = jnp.concatenate(
        [rev_cum[:, 1:], jnp.zeros((n, 1), jnp.float32)], axis=1)
    phi = jnp.arctan2(jnp.sqrt(tail[:, :d - 1]), pts[:, :d - 1])  # [0, pi/2]
    slot = jnp.clip(jnp.floor(2.0 * phi / jnp.pi * m), 0, m - 1)
    radix = (m ** jnp.arange(d - 1, dtype=jnp.int32))
    return jnp.sum(slot.astype(jnp.int32) * radix[None, :], axis=1)


def sliced_part_ids(pts: jnp.ndarray, mask: jnp.ndarray, p: int,
                    dim: int = 0) -> jnp.ndarray:
    """SLICED (paper §3.4): sort on one dimension (index tie-break -> total
    order), cut into p equal runs: p(t) = floor(rank * p / N_valid)."""
    n = pts.shape[0]
    v = jnp.where(mask, pts[:, dim], jnp.inf)
    order = jnp.argsort(v)  # stable -> tie-break by original index
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    nvalid = jnp.maximum(jnp.sum(mask), 1)
    return jnp.clip((ranks * p) // nvalid, 0, p - 1).astype(jnp.int32)


# --------------------------------------------------------------------------
# Partition-count helpers (paper §5.2: p is m^d for GRID, m^(d-1) for
# ANGULAR — choose m to get closest to the target p)
# --------------------------------------------------------------------------

def grid_num_parts(m: int, d: int) -> int:
    return m ** d


def angular_num_parts(m: int, d: int) -> int:
    return m ** (d - 1)


def slices_for_target_parts(target_p: int, dims: int) -> int:
    """Closest m >= 1 such that m^dims ~ target_p."""
    m = max(1, round(target_p ** (1.0 / dims)))
    best, best_gap = m, abs(m ** dims - target_p)
    for cand in (m - 1, m + 1, m + 2):
        if cand >= 1 and abs(cand ** dims - target_p) < best_gap:
            best, best_gap = cand, abs(cand ** dims - target_p)
    return best


# --------------------------------------------------------------------------
# Routing: tuples -> fixed-capacity buckets
# --------------------------------------------------------------------------

def bucketize(pts: jnp.ndarray, mask: jnp.ndarray, ids: jnp.ndarray, p: int,
              capacity: int) -> Buckets:
    """Route tuples to (p, capacity) buckets with validity masks.

    One stable sort by partition id (invalid rows sort to a virtual
    partition p) carries the row indices along; a binary search for each
    partition's first and one-past-last position in that order gives its
    start and count, and each bucket slot (j, s) then reads the row at
    sorted position start[j] + s by one gather. Rows keep their input order
    within a partition; rows beyond capacity are dropped and flagged as
    overflow. `ids` must lie in [0, p).
    """
    n, d = pts.shape
    ids_eff = jnp.where(mask, ids, p).astype(jnp.int32)
    ids_s, order = lax.sort((ids_eff, jnp.arange(n, dtype=jnp.int32)),
                            num_keys=1, is_stable=True)
    # (p, 2) bounds, so that a mesh sharding the buckets by partition finds
    # each partition's start and end on the same worker
    part = jnp.arange(p, dtype=jnp.int32)[:, None]
    bounds = jnp.searchsorted(
        ids_s, part + jnp.arange(2, dtype=jnp.int32)).astype(jnp.int32)
    starts, counts = bounds[:, 0], bounds[:, 1] - bounds[:, 0]
    overflow = jnp.any(counts > capacity)
    # each bucket's row indices are one contiguous run of `order`
    order_pad = jnp.concatenate([order, jnp.zeros((capacity,), jnp.int32)])
    rows = jax.vmap(lambda s: lax.dynamic_slice(order_pad, (s,), (capacity,)))(
        starts)
    valid = jnp.arange(capacity, dtype=jnp.int32)[None, :] < counts[:, None]
    # an empty table still gives the gather one row, which no slot keeps
    table = pts if n else jnp.zeros((1, d), pts.dtype)
    points = jnp.where(valid[..., None], table[rows],
                       jnp.asarray(SENTINEL, pts.dtype))
    return Buckets(points, valid, counts, overflow)
