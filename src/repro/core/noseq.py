"""NoSeq (paper §4.2): fully parallel second phase.

After phase 1, let u = union of local skylines u_i. Worker i removes its
globally-dominated tuples by testing u_i only against its *potential
dominators* pd_i subset of u \\ u_i (Proposition 2):

  RANDOM / ANGULAR : pd_i = u \\ u_i                (no inter-partition order)
  SLICED           : pd_i = { u_j : j < i }         (slice order is
                      topological w.r.t. the sliced dimension)
                    + { t in u_j : j > i, t[s] <= max of u_i[s] }
  GRID             : pd_i = { u_j : c_j <=_G c_i }  (a dominator's cell
                      coordinates are <= in every dimension)

SLICED's second term is for ties: slices are cut from a sort on the
sliced attribute s with the row index as tie-break, so rows with equal
values of s can fall on both sides of a boundary, and a row of a later
slice dominates a row of slice i only if its value of s equals that
row's (which is then the largest of slice i).

The masks below are evaluated per reference *row* of the gathered buffer
(p * C rows), given the row's source partition.  The phase-2 dominance
test runs under the kernel name `KERNEL_NAME`, so a device trace tells
it from the representative filter's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dominance import dominated_mask

__all__ = ["KERNEL_NAME", "pd_row_mask", "slice_max", "pd_refs",
           "relative_skyline_mask", "relative_rows_mask"]

KERNEL_NAME = "noseq_mask"


def slice_max(points: jnp.ndarray, mask: jnp.ndarray,
              dim: int) -> jnp.ndarray:
    """(...,) the largest valid value of attribute ``dim`` of each
    (..., C, d) partition buffer (-inf for an empty one)."""
    return jnp.max(jnp.where(mask, points[..., dim], -jnp.inf), axis=-1)


def pd_row_mask(strategy: str, own_part: jnp.ndarray,
                ref_parts: jnp.ndarray,
                own_cell: jnp.ndarray | None = None,
                ref_cells: jnp.ndarray | None = None,
                own_max: jnp.ndarray | None = None,
                ref_vals: jnp.ndarray | None = None) -> jnp.ndarray:
    """(R,) bool — which gathered rows are potential dominators for the
    worker that owns partition `own_part`.  SLICED also reads the
    largest sliced value of the own partition (`slice_max`) and the
    rows' sliced values."""
    not_self = ref_parts != own_part
    if strategy in ("random", "angular"):
        return not_self
    if strategy == "sliced":
        assert own_max is not None and ref_vals is not None
        return (ref_parts < own_part) | ((ref_parts > own_part)
                                         & (ref_vals <= own_max))
    if strategy == "grid":
        assert own_cell is not None and ref_cells is not None
        weak = jnp.all(ref_cells <= own_cell[None, :], axis=-1)
        return weak & not_self
    raise ValueError(f"unknown strategy {strategy!r}")


def pd_refs(strategy: str, own_parts, own_cells, own_maxes, ref_parts,
            ref_cells, ref_vals, ref_mask) -> jnp.ndarray:
    """() int32: the valid potential-dominator rows of every partition
    (`pd_row_mask` of each of the P own partitions over R rows), summed
    over the partitions."""
    pd = jax.vmap(lambda part, cell, mx: pd_row_mask(
        strategy, part, ref_parts, cell, ref_cells, mx, ref_vals))(
            own_parts, own_cells, own_maxes)
    return jnp.sum(pd & ref_mask[None, :], dtype=jnp.int32)


def relative_skyline_mask(u_i: jnp.ndarray, mask_i: jnp.ndarray,
                          refs: jnp.ndarray, ref_mask: jnp.ndarray,
                          pd_mask: jnp.ndarray, *,
                          impl: str = "auto") -> jnp.ndarray:
    """SKY_{pd_i}(u_i) membership mask (paper Definition 4)."""
    dom = dominated_mask(u_i, refs, ref_mask & pd_mask, impl=impl,
                         name=KERNEL_NAME)
    return mask_i & ~dom


def relative_rows_mask(pts: jnp.ndarray, mask: jnp.ndarray,
                       parts: jnp.ndarray, cells: jnp.ndarray, *,
                       strategy: str, block: int = 256,
                       dim: int = 0) -> jnp.ndarray:
    """Per-ROW relative-skyline mask of a mixed-origin buffer.

    The flat NoSeq merge evaluates pd_i once per *worker* (every row of
    u_i shares one partition). The tree merge's intermediate buffers mix
    rows from many partitions, so each row carries its own partition id
    (and grid cell) and the potential-dominator relation is evaluated
    per (candidate row, reference row) pair — the same pd predicate as
    `pd_row_mask`, just row-wise on both sides (SLICED: a later slice's
    row counts when its value of attribute ``dim`` is <= the
    candidate's). The dominance test is pure boolean comparisons, so
    the outcome is bit-identical to the blocked kernel's for the same
    pair set; candidates walk in blocks of ``block`` rows (a `lax.map`)
    to keep the pairwise footprint at O(block x R) like the kernel's.
    """
    r, d = pts.shape
    b = min(block, max(r, 1))
    nb = -(-r // b)
    pad = nb * b - r
    cp = jnp.pad(pts, ((0, pad), (0, 0)))
    cm = jnp.pad(mask, (0, pad))
    cparts = jnp.pad(parts, (0, pad))
    ccells = jnp.pad(cells, ((0, pad), (0, 0)))

    def one(args):
        x, xm, xp, xc = args
        le = jnp.all(pts[None, :, :] <= x[:, None, :], axis=-1)
        lt = jnp.any(pts[None, :, :] < x[:, None, :], axis=-1)
        if strategy in ("random", "angular"):
            pd = parts[None, :] != xp[:, None]
        elif strategy == "sliced":
            pd = ((parts[None, :] < xp[:, None])
                  | ((parts[None, :] > xp[:, None])
                     & (pts[None, :, dim] <= x[:, None, dim])))
        elif strategy == "grid":
            pd = (jnp.all(cells[None, :, :] <= xc[:, None, :], axis=-1)
                  & (parts[None, :] != xp[:, None]))
        else:
            raise ValueError(f"unknown strategy {strategy!r}")
        dom = jnp.any(le & lt & mask[None, :] & pd, axis=1)
        return xm & ~dom

    out = jax.lax.map(one, (cp.reshape(nb, b, d), cm.reshape(nb, b),
                            cparts.reshape(nb, b),
                            ccells.reshape(nb, b, ccells.shape[-1])))
    return out.reshape(-1)[:r]
