"""Pallas TPU kernel for blocked dominance tests.

This is the compute hot-spot of skyline computation (paper §2: the
intrinsically quadratic dominance tests). The kernel computes, for a tile
of candidate points against a tile of reference points, whether each
candidate is dominated by any valid reference.

TPU-native layout (see DESIGN.md §3): points are stored **transposed** as
``(d_pad, N)`` so that the point index runs along the 128-wide lane
dimension and the (small, 2..8) attribute dimension sits in sublanes. The
pairwise comparison for one attribute k is then a rank-1 broadcast
``refs[k, :, None] <= cands[k, None, :]`` producing a well-shaped
``(BR, BC)`` VPU tile; the AND/OR reductions over the d attributes are a
short unrolled loop. This replaces SFS's scalar window scan with uniform
vector work while preserving its semantics (ops.py / sfs.py drive it in
score-sorted order, so the ``lower_tri`` mode implements the topological-
order property of the sort).

Grid: ``(num_cand_blocks, num_ref_blocks)`` with the ref-block index
innermost, so each output tile stays resident while it accumulates the
OR over all reference blocks.

VMEM per step (defaults BC=BR=512, d_pad=8, fp32): 2.1 MiB as compiled
by Mosaic for a v5e, mostly the per-attribute (BR, 1) reference columns
and the (BR, BC) masks (`dominance_vmem_bytes` bounds it) — well under
the 16 MiB default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["dominated_mask_pallas", "dominance_vmem_bytes", "D_PAD"]

D_PAD = 8  # attribute dim padded to one fp32 sublane tile


def _block_dominated(x, r, m, *, d: int, block_c: int, block_r: int,
                     lower_tri: bool, roff, coff):
    """(BC,) bool: each candidate of the ``(d, BC)`` tile dominated by a
    valid reference of the ``(d, BR)`` tile — the SHARED per-tile body
    of the TPU kernel below and the GPU kernel (gpu.py).  ``roff`` /
    ``coff`` are the tiles' global row/column offsets (only consulted in
    ``lower_tri`` self-join mode)."""
    le = jnp.ones((block_r, block_c), dtype=jnp.bool_)
    lt = jnp.zeros((block_r, block_c), dtype=jnp.bool_)
    for k in range(d):  # unrolled: d is a static 2..8 (padded rows inert)
        rk = r[k, :][:, None]   # (BR, 1)
        xk = x[k, :][None, :]   # (1, BC)
        le = le & (rk <= xk)
        lt = lt | (rk < xk)
    dom = le & lt & (m[0, :][:, None] > 0)

    if lower_tri:
        rid = roff + jax.lax.broadcasted_iota(
            jnp.int32, (block_r, block_c), 0)
        cid = coff + jax.lax.broadcasted_iota(
            jnp.int32, (block_r, block_c), 1)
        dom = dom & (rid < cid)

    return jnp.any(dom, axis=0)  # (BC,)


def _dominance_kernel(cands_ref, refs_ref, mask_ref, out_ref, *, d: int,
                      block_c: int, block_r: int, lower_tri: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    red = _block_dominated(
        cands_ref[...], refs_ref[...], mask_ref[...], d=d,
        block_c=block_c, block_r=block_r, lower_tri=lower_tri,
        roff=j * block_r, coff=i * block_c)
    out_ref[...] = out_ref[...] | red[None, :].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("lower_tri", "block_c", "block_r", "interpret", "name"))
def dominated_mask_pallas(
    cands_t: jnp.ndarray,
    refs_t: jnp.ndarray,
    ref_mask: jnp.ndarray,
    *,
    lower_tri: bool = False,
    block_c: int = 512,
    block_r: int = 512,
    interpret: bool = False,
    name: str = "dominated_mask",
) -> jnp.ndarray:
    """Blocked dominance-test kernel.

    Args:
      cands_t: (D_PAD, C) transposed candidates; C % block_c == 0.
      refs_t:  (D_PAD, R) transposed references; R % block_r == 0.
      ref_mask: (1, R) int32 validity (0 = padding / invalid row).
      lower_tri: self-join mode — ref j may only dominate cand i if j < i
        (global indices). Requires cands_t and refs_t to be the same array.
      interpret: run the kernel body in interpret mode (CPU validation).
      name: the kernel's name in the compiled program, which a device
        trace shows as the op's name.

    Returns:
      (1, C) int32 — nonzero where the candidate is dominated.
    """
    d_pad, c = cands_t.shape
    _, r = refs_t.shape
    assert d_pad == D_PAD, f"attribute dim must be padded to {D_PAD}"
    assert c % block_c == 0 and r % block_r == 0, (c, r, block_c, block_r)

    grid = (c // block_c, r // block_r)
    kernel = functools.partial(
        _dominance_kernel, d=d_pad, block_c=block_c, block_r=block_r,
        lower_tri=lower_tri)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((D_PAD, block_c), lambda i, j: (0, i)),
            pl.BlockSpec((D_PAD, block_r), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.int32),
        name=name,
        interpret=interpret,
    )(cands_t, refs_t, ref_mask)


def dominance_vmem_bytes(*, block_c: int, block_r: int,
                         itemsize: int = 4) -> int:
    """Upper bound on the dominance kernel's scoped VMEM, in bytes: the
    double-buffered attribute/mask/output blocks, each attribute's
    ``(BR, 1)`` reference column at a full 128-lane row of 32-bit words,
    and the ``(BR, BC)`` le/lt masks at 32 bits (the accounting of
    `repro.kernels.sfs.kernel.sweep_vmem_bytes`).  Mosaic needs 2.1 MiB
    at BR = BC = 512 (compiled for a described v5e).  Gated per
    compiled configuration by the static verifier (`repro.analysis`)."""
    io = 2 * (D_PAD * (block_c + block_r) * itemsize
              + 8 * (block_r + block_c) * 4)    # + mask, out (int32)
    columns = D_PAD * block_r * 128 * 4
    tests = 2 * 4 * block_r * block_c           # le, lt
    return io + columns + tests
