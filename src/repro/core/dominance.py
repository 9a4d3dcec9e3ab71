"""Dominance primitives (paper Definitions 1 and 2).

Point sets are masked: ``(pts: (N, d) f32, mask: (N,) bool)``. Invalid rows
additionally carry the ``SENTINEL`` coordinate so that, even if a mask is
dropped by mistake, a sentinel row can never dominate a real point (defense
in depth; the masks remain authoritative).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dominance import dominated_mask as _dominated_mask
from repro.kernels.dominance import dominance_matrix_ref

__all__ = [
    "SENTINEL", "dominates", "dominance_matrix", "dominated_mask",
    "region_volume", "monotone_score", "canonical_order", "apply_sentinel",
]

# Large-but-finite: sums of up to 8 sentinels stay finite in f32? They do
# not (8 * 1.7e38 overflows) — inf from an overflowed sentinel score still
# sorts last, which is exactly what we need.
SENTINEL = jnp.float32(1.7e38)


def dominates(t: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Scalar predicate: does point t dominate point s?"""
    return jnp.all(t <= s) & jnp.any(t < s)


def dominance_matrix(refs: jnp.ndarray, cands: jnp.ndarray) -> jnp.ndarray:
    """(R, C) bool: out[j, i] = refs[j] dominates cands[i] (small inputs)."""
    return dominance_matrix_ref(refs, cands)


def dominated_mask(cands, refs, ref_mask=None, *, lower_tri=False,
                   impl="auto", name="dominated_mask"):
    """Blocked kernel entry point (see kernels/dominance/ops.py)."""
    return _dominated_mask(cands, refs, ref_mask, lower_tri=lower_tri,
                           impl=impl, name=name)


def region_volume(pts: jnp.ndarray) -> jnp.ndarray:
    """Hyper-volume of the dominance region on [0,1]^d (paper §4.1):
    V(DR(t)) = prod_i (1 - t[i]). Values outside [0,1] clamp to volume 0
    contribution-wise (REGION requires normalized data, paper §4.1)."""
    return jnp.prod(jnp.clip(1.0 - pts, 0.0, 1.0), axis=-1)


def monotone_score(pts: jnp.ndarray, mask: jnp.ndarray | None = None
                   ) -> jnp.ndarray:
    """The strictly monotone scoring function used for SFS presorting
    (f = sum of attributes). Invalid rows score +inf so they sort last.
    Strict monotonicity gives the topological-order property: t < s implies
    score(t) < score(s)."""
    s = jnp.sum(pts, axis=-1)
    if mask is not None:
        s = jnp.where(mask, s, jnp.inf)
    return s


def canonical_order(pts: jnp.ndarray, mask: jnp.ndarray | None = None
                    ) -> jnp.ndarray:
    """Permutation sorting by monotone score with lexicographic
    coordinates as tie-break — a *total* order on point values, so the
    result is independent of the input permutation. Equal-score points
    can never dominate each other (t < s implies score(t) < score(s)),
    so any tie order is a valid SFS topological order; fixing it
    lexicographically is what makes canonicalized buffers bitwise
    comparable across execution paths (one-shot vs any chunking —
    repro.core.incremental relies on this). Invalid rows sort last.

    Computed as d+1 stable single-key sorts, least significant key
    first — exactly the permutation of one multi-key lexsort, which
    XLA's TPU compiler takes minutes over (318 s for a vmapped 5-key
    sort of 32 x 4,096 rows, against 16 s this way)."""
    score = monotone_score(pts, mask)
    order = jnp.arange(pts.shape[0], dtype=jnp.int32)
    for key in [pts[:, j] for j in reversed(range(pts.shape[1]))] + [score]:
        order = order[jnp.argsort(key[order])]
    return order


def apply_sentinel(pts: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Overwrite invalid rows with the sentinel coordinate."""
    return jnp.where(mask[..., None], pts, SENTINEL)
