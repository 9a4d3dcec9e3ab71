"""Skyline algorithms: naive oracle and block-SFS (paper Algorithm 1,
adapted to TPU-style blocked execution — DESIGN.md §3 change (1)).

The local phase is ONE call: :func:`local_skyline_batch` sorts a batch of
partitions by a strictly monotone score (topological order w.r.t.
dominance) and hands the whole batch to the fused SFS sweep
(:func:`repro.kernels.sfs.sfs_sweep`) — a single dispatch that carries
each partition's window buffer and count through the entire scan, with
the in-block lower-triangular self-test fused in.  The backend layer
(repro.kernels.backend) picks the sweep implementation from the ``impl``
string: the compiled Pallas grid on TPU, the blocked single-dispatch jnp
sweep elsewhere, interpret mode for CPU validation of the kernel body,
and the legacy per-pair reference for tests/benchmarks.  All of them are
bit-for-bit equivalent (tests/test_sfs_kernel.py).

block_sfs keeps SFS's O(N * |SKY|) work profile and its exactness
argument: transitivity makes the blocked formulation exact — if the only
in-block dominator of t is itself dominated by a window tuple w, then w
dominates t too, so t is still eliminated by the window test.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.dominance import (SENTINEL, apply_sentinel, dominated_mask,
                                  monotone_score)
from repro.kernels.backend import resolve_spec
from repro.kernels.sfs import sfs_sweep

__all__ = ["SkyBuffer", "naive_skyline_mask", "skyline_mask", "block_sfs",
           "local_skyline_batch", "compact", "compact_order"]


class SkyBuffer(NamedTuple):
    """Fixed-capacity masked skyline buffer (static shapes for JAX)."""
    points: jnp.ndarray    # (C, d) packed members (leading axes allowed)
    mask: jnp.ndarray      # (C,) bool
    count: jnp.ndarray     # () int32 — true skyline size (may exceed C)
    overflow: jnp.ndarray  # () bool — True iff count > C


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def naive_skyline_mask(pts: jnp.ndarray, mask: jnp.ndarray | None = None,
                       ) -> jnp.ndarray:
    """O(N^2) full-matrix oracle; returns membership mask in input order."""
    if mask is None:
        mask = jnp.ones(pts.shape[0], jnp.bool_)
    from repro.kernels.dominance import dominated_mask_ref
    dom = dominated_mask_ref(pts, pts, mask)
    return mask & ~dom


def skyline_mask(pts: jnp.ndarray, mask: jnp.ndarray | None = None, *,
                 impl: str = "auto") -> jnp.ndarray:
    """Blocked O(N^2) skyline membership mask (memory-bounded)."""
    if mask is None:
        mask = jnp.ones(pts.shape[0], jnp.bool_)
    dom = dominated_mask(pts, pts, mask,
                         impl=resolve_spec(impl).dominance)
    return mask & ~dom


def local_skyline_batch(pts: jnp.ndarray, mask: jnp.ndarray | None = None,
                        *, capacity: int, block: int = 256,
                        impl: str = "auto", wtile: int = 0) -> SkyBuffer:
    """Blocked Sort-Filter-Skyline of a (P, N, d) partition batch in one
    fused-sweep dispatch.

    Every leaf of the returned :class:`SkyBuffer` carries a leading P
    axis.  Exact per partition whenever |SKY| <= capacity (the overflow
    flag reports violations; extra tuples are dropped, never spurious
    ones added — the result is then a subset of the skyline).

    ``wtile`` is the sweep's window-tile width (0 = whole window per
    candidate block): tiling bounds the kernel's resident comparison
    footprint at O(wtile x block) instead of O(capacity x block) without
    changing a single output bit — see `repro.kernels.sfs`.

    Precondition (repo-wide SENTINEL convention, see repro.core.
    dominance): valid data coordinates stay below ``SENTINEL`` — the
    sweeps rely on sentinel-filled rows being inert in dominance tests
    instead of carrying runtime validity masks.
    """
    if pts.ndim != 3:
        raise ValueError(f"expected a (P, N, d) batch, got {pts.shape}")
    p, n, d = pts.shape
    if mask is None:
        mask = jnp.ones((p, n), jnp.bool_)
    block = min(block, max(n, 1))
    spec = resolve_spec(impl)

    # Sort-Filter: presort every partition by the strictly monotone score
    # (dominators sort strictly earlier), sentinel-fill invalid rows, and
    # block-pad — identical bytes reach every sweep implementation.
    score = monotone_score(pts, mask)
    order = jnp.argsort(score, axis=-1)
    mask_s = jnp.take_along_axis(mask, order, 1)
    pts_s = apply_sentinel(jnp.take_along_axis(pts, order[..., None], 1),
                           mask_s)

    npad = _ceil_to(max(n, 1), block)
    pts_p = jnp.full((p, npad, d), SENTINEL, pts.dtype)
    pts_p = pts_p.at[:, :n].set(pts_s)
    mask_p = jnp.zeros((p, npad), jnp.bool_).at[:, :n].set(mask_s)

    wcap = _ceil_to(capacity, block)
    window, wmask, count = sfs_sweep(pts_p, mask_p, block=block, wcap=wcap,
                                     sentinel=float(SENTINEL),
                                     wtile=wtile, spec=spec)
    return SkyBuffer(window, wmask, count, count > capacity)


def block_sfs(pts: jnp.ndarray, mask: jnp.ndarray | None = None, *,
              capacity: int, block: int = 256, impl: str = "auto",
              wtile: int = 0) -> SkyBuffer:
    """Blocked Sort-Filter-Skyline of ONE point set: a thin wrapper over
    the batched fused-sweep entry (:func:`local_skyline_batch`) with a
    single partition.  Exact whenever |SKY| <= capacity (overflow flag
    reports violations; the result is then a subset of the skyline)."""
    buf = local_skyline_batch(
        pts[None], None if mask is None else mask[None],
        capacity=capacity, block=block, impl=impl, wtile=wtile)
    return SkyBuffer(buf.points[0], buf.mask[0], buf.count[0],
                     buf.overflow[0])


def compact_order(mask: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """The row order `compact` gathers by: stable valid-rows-first,
    truncated to ``capacity``.  Exposed so callers carrying side columns
    (partition ids, grid cells) can reorder them identically and share
    `compact`'s overflow accounting.

    A counting pass — each row's destination from two prefix sums, then
    one scatter — gives exactly the permutation of a stable argsort of
    ``~mask`` without a sort: XLA's TPU compiler spends tens of seconds
    on each sort of tens of thousands of rows, and this runs in every
    merge and state update."""
    n = mask.shape[0]
    m = mask.astype(jnp.int32)
    dest = jnp.where(mask, jnp.cumsum(m) - 1,
                     jnp.sum(m) + jnp.cumsum(1 - m) - 1)
    return jnp.zeros((min(n, capacity),), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop", unique_indices=True)


def compact(pts: jnp.ndarray, mask: jnp.ndarray, capacity: int) -> SkyBuffer:
    """Stable-move valid rows to the front; truncate to capacity."""
    order = compact_order(mask, capacity)
    mask_c = mask[order]
    pts_c = apply_sentinel(pts[order], mask_c)
    count = jnp.sum(mask).astype(jnp.int32)
    return SkyBuffer(pts_c, mask_c, count, count > capacity)
