"""Pallas TPU kernel for the fused local-phase SFS sweep.

One ``pallas_call`` executes the *entire* sorted Sort-Filter-Skyline scan
for a batch of partitions: grid ``(partition, candidate_block)`` with the
candidate-block index innermost, so each partition's window buffer and
running count stay resident in on-chip memory across its whole scan (the
window in a revisited output block — the same residency trick the
blocked dominance kernel uses for its OR-accumulator — the count in
SMEM scratch).  This replaces the seed's one-kernel-dispatch-per
(window-block, candidate-block) pair inside an XLA ``fori_loop``: the
window test, the lower-triangular in-block self-test and the append are
fused into a single kernel body, so a whole partition batch is one launch
with no host-visible intermediate state.

Layout follows the dominance kernel (DESIGN.md §3): points are stored
transposed as ``(d_pad, N)`` so the point index runs along the 128-wide
lane dimension and the (small, 2..8) attribute dimension sits in sublanes;
per-attribute comparisons are rank-1 ``(W, BC)`` / ``(BC, BC)`` VPU
broadcasts unrolled over the static ``d``.  The append is scatter-free: a
one-hot ``(BC, W)`` slot map built from the in-block prefix count routes
each kept candidate to its window slot with a masked integer-bit sum
(exactly one non-zero contributor per slot and integer adds are exact,
so the copy preserves every bit, -0.0 included), which keeps the kernel
free of dynamic-index stores.

Each partition's scan stops at its last block that holds a valid row:
the per-partition block bound is a scalar-prefetched operand, a step
past it does nothing, and its candidate index map revisits the last
swept block so the pipeline fetches nothing new.  Valid rows sort
first, so after the representative filter and in a compacted merge
buffer most of a padded bucket is such a tail; a block with no valid
row keeps nothing, so skipping it changes no output bit.

Semantics are bit-for-bit those of the per-pair reference
(:func:`repro.kernels.sfs.ref.sfs_sweep_perpair`, the seed ``block_sfs``
body): identical keep decisions, identical slot assignment (first ``W``
keeps in score order, later keeps dropped), identical running count.

Mosaic layout rules the kernel keeps: the running count is a scalar in
SMEM (scratch), written out as one lane row per partition; the mask and
count blocks carry a squeezed leading partition dim so their last two
block dims equal the array's for any P; the window's validity mask is
not an output at all (the window is packed, so the caller derives it
from the count).

VMEM (the tiling contract new backends must keep): untiled (``wtile=0``)
the window test materializes ``(W, BC)`` masks and, per attribute, a
``(W, 1)`` window column that Mosaic lays out as a full 128-lane row —
about 4 KiB per window slot, so W=4096 needs 16.2 MiB at BC=256.  With
``wtile=T`` the window test and the append iterate over W/T window
sub-blocks (`_tiled_block_step`), so the intermediates shrink to the
T-slot tile and only the ``(d_pad, W)`` window buffer itself scales
with W (1.4 MiB at W=4096, T=512; 8 MiB at W=262144).
`sweep_vmem_bytes` bounds both laws; the kernel asks Mosaic for that
bound as its scoped-VMEM limit when it exceeds the default, and the
compiled TPU path always tiles windows wider than one tile
(`repro.kernels.sfs.ops.tpu_geometry`).  Dynamic window slices start
on a multiple of the 128-lane tile there.  All tilings are bit-for-bit
identical (the tile only changes the schedule, never a keep decision).
Interpret mode (the CPU validation path) has no such limits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sfs_sweep_pallas", "sweep_vmem_bytes", "vmem_limit_bytes",
           "D_PAD", "LANE"]

D_PAD = 8   # attribute dim padded to one fp32 sublane tile
LANE = 128  # TPU lane width: dynamic window slices start on a multiple


def _self_test(x, *, d: int, block_c: int):
    """(BC,) bool: dominated within the block by an earlier (smaller-
    score) row — the SFS topological-order property makes this lower-
    triangular (invalid rows are sentinel-filled, hence inert as refs)."""
    le_s = jnp.ones((block_c, block_c), jnp.bool_)
    lt_s = jnp.zeros((block_c, block_c), jnp.bool_)
    for k in range(d):
        xr = x[k, :][:, None]
        xc = x[k, :][None, :]
        le_s = le_s & (xr <= xc)
        lt_s = lt_s | (xr < xc)
    rid = jax.lax.broadcasted_iota(jnp.int32, (block_c, block_c), 0)
    cid = jax.lax.broadcasted_iota(jnp.int32, (block_c, block_c), 1)
    return jnp.any(le_s & lt_s & (rid < cid), axis=0)


def _append(x, pos, base, width: int, cur):
    """Rows of the ``width``-slot window sub-block starting at slot
    ``base`` after the append: candidate ``c`` lands in slot ``pos[c]``
    (-1 for candidates not kept).  Scatter-free: a one-hot
    ``(BC, width)`` slot map routes each kept candidate with a masked
    sum over the INTEGER BITS of its values — exactly one non-zero
    contributor per slot and integer addition is exact, so the copy
    preserves every bit (including -0.0, which a float sum would flip to
    +0.0).  Keeps past the window match no slot and are dropped (the
    reference's ``mode="drop"``)."""
    block_c = x.shape[1]
    slot = base + jax.lax.broadcasted_iota(jnp.int32, (block_c, width), 1)
    onehot = pos[:, None] == slot                            # (BC, width)
    newrow = jnp.any(onehot, axis=0)                         # (width,)
    ibits = {4: jnp.int32, 2: jnp.int16, 1: jnp.int8}[
        jnp.dtype(x.dtype).itemsize]
    izero = jnp.zeros((), ibits)
    rows = []
    for k in range(x.shape[0]):
        xb = jax.lax.bitcast_convert_type(x[k, :], ibits)    # (BC,)
        vals = jnp.sum(jnp.where(onehot, xb[:, None], izero), axis=0)
        row = jax.lax.bitcast_convert_type(vals, x.dtype)    # (width,)
        rows.append(jnp.where(newrow, row, cur[k, :]))
    return jnp.stack(rows)


def _keep_slots(x, xm, domw, count, *, d: int, block_c: int):
    """Window slot of each candidate of one block that is kept (valid,
    not dominated by the window, not dominated within the block) —
    ``count + |kept earlier in block|`` — or -1, and the number kept.
    The in-block prefix count is a (BC, BC) masked reduction (no cumsum
    primitive needed on the lane axis)."""
    keep = xm & ~domw & ~_self_test(x, d=d, block_c=block_c)
    ki = keep.astype(jnp.int32)
    rid = jax.lax.broadcasted_iota(jnp.int32, (block_c, block_c), 0)
    cid = jax.lax.broadcasted_iota(jnp.int32, (block_c, block_c), 1)
    prefix = jnp.sum(jnp.where(rid <= cid, ki[:, None], 0), axis=0)
    return jnp.where(keep, count + prefix - 1, -1), jnp.sum(ki)


def _window_dominated(w, x, *, d: int):
    """(BC,) bool: candidate dominated by a member of the ``(d_pad, T)``
    window tile ``w``.  No validity mask: empty slots hold the sentinel
    coordinate in every attribute and cannot dominate data below it."""
    le = None
    lt = None
    for k in range(d):  # unrolled: d is a static 2..8
        wk = w[k, :][:, None]    # (T, 1)
        xk = x[k, :][None, :]    # (1, BC)
        le = (wk <= xk) if le is None else le & (wk <= xk)
        lt = (wk < xk) if lt is None else lt | (wk < xk)
    return jnp.any(le & lt, axis=0)


def _tiled_block_step(x, xm, count, win_ref, *, d: int, block_c: int,
                      wcap: int, wtile: int):
    """One candidate-block step of the sweep with the window iterated in
    ``wtile``-column sub-blocks — the SHARED kernel body of the tiled TPU
    path and the GPU backend (gpu.py), which both hold the window in a
    ``(d_pad, W)`` ref revisited across the scan.

    Never materializes more than ``wtile * block_c`` test elements at
    once: the window test is a fori_loop over the live tiles (slots past
    ``count`` hold the sentinel and are inert, so any tile bound >= live
    is exact) and the append touches only the tiles its slot range
    [count, count+kept) intersects.  Keep decisions, slot assignment and
    count are bit-for-bit the untiled body's.  Returns the new count."""
    ntiles = wcap // wtile
    live = jnp.minimum(
        (jnp.minimum(count, wcap) + wtile - 1) // wtile, ntiles)

    def tile(t):
        return pl.ds(pl.multiple_of(t * wtile, wtile), wtile)

    def wbody(t, acc):
        dom = _window_dominated(win_ref[:, tile(t)], x, d=d)
        return acc | dom.astype(jnp.int32)

    domw = jax.lax.fori_loop(0, live, wbody,
                             jnp.zeros((block_c,), jnp.int32)) > 0
    pos, kept = _keep_slots(x, xm, domw, count, d=d, block_c=block_c)
    # kept candidates land in slots [count, count+kept), so only tiles
    # intersecting that range are visited (none once the window
    # overflowed: lo == hi)
    lo = jnp.minimum(count // wtile, ntiles)
    hi = jnp.minimum((count + kept + wtile - 1) // wtile, ntiles)

    def abody(t, carry):
        win_ref[:, tile(t)] = _append(x, pos, t * wtile, wtile,
                                      win_ref[:, tile(t)])
        return carry

    jax.lax.fori_loop(lo, hi, abody, jnp.int32(0))
    return count + kept


def _sfs_sweep_kernel(nblk_ref, cands_ref, mask_ref, win_ref, count_ref,
                      count_smem, *, d: int, block_c: int, wcap: int,
                      wtile: int, sentinel):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        win_ref[...] = jnp.full(win_ref.shape, sentinel, win_ref.dtype)
        count_smem[0] = jnp.int32(0)

    # blocks past the partition's last valid row keep nothing: skip them
    @pl.when(j < nblk_ref[i])
    def _step():
        x = cands_ref[...]           # (D_PAD, BC)
        xm = mask_ref[0, :] > 0      # (BC,)
        count = count_smem[0]        # () int32, scalar memory
        if wtile:  # window-tiled step: resident tests bounded at T x BC
            new = _tiled_block_step(x, xm, count, win_ref, d=d,
                                    block_c=block_c, wcap=wcap,
                                    wtile=wtile)
        else:      # the whole resident window tested at once
            w = win_ref[...]         # (D_PAD, W)
            domw = _window_dominated(w, x, d=d)
            pos, kept = _keep_slots(x, xm, domw, count, d=d,
                                    block_c=block_c)
            win_ref[...] = _append(x, pos, 0, wcap, w)
            new = count + kept
        count_smem[0] = new

    count_ref[...] = jnp.full(count_ref.shape, count_smem[0], jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "wcap", "wtile", "sentinel", "interpret"))
def sfs_sweep_pallas(
    cands_t: jnp.ndarray,
    mask: jnp.ndarray,
    nblk: jnp.ndarray,
    *,
    block_c: int,
    wcap: int,
    sentinel: float,
    wtile: int = 0,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused SFS sweep over a batch of score-sorted partitions.

    Args:
      cands_t: (P * D_PAD, N) transposed candidates, each partition's rows
        presorted by a strictly monotone score with invalid rows holding
        the sentinel coordinate; N % block_c == 0.  Attribute rows past
        the true d are zero (inert for the comparisons, never extracted).
      mask: (P, N) int32 row validity (0 = padding / invalid).
      nblk: (P,) int32 candidate blocks to sweep per partition: every
        block from ``nblk[i]`` on must hold no valid row of partition
        ``i`` (`repro.kernels.sfs.ops.sweep_blocks` gives the least such
        bound).  Those blocks would keep nothing, so they are neither
        fetched nor tested, and the result is that of the full sweep.
      block_c: candidate block (grid step) size.
      wcap: window capacity in rows (a multiple of the dominance block by
        construction in the caller).
      sentinel: fill value for empty window slots.
      wtile: window tile width — 0 tests the whole window per step
        (resident O(wcap x block_c)); a divisor of ``wcap`` iterates the
        test/append over wtile-column sub-blocks (resident
        O(wtile x block_c), bit-identical; see `_tiled_block_step`).
      interpret: run the kernel body in interpret mode (CPU validation).

    Returns:
      ``(window_t (P * D_PAD, wcap), count (P,) int32)`` — the packed
      per-partition skyline window in the same transposed layout and the
      total number of kept (skyline) rows, which may exceed ``wcap``
      under overflow.  The window is packed: its valid slots are exactly
      ``[0, min(count, wcap))``.
    """
    pd_pad, n = cands_t.shape
    assert pd_pad % D_PAD == 0, pd_pad
    p = pd_pad // D_PAD
    assert mask.shape == (p, n), (mask.shape, p, n)
    assert nblk.shape == (p,), (nblk.shape, p)
    assert n % block_c == 0, (n, block_c)
    assert wtile == 0 or wcap % wtile == 0, (wcap, wtile)
    d = D_PAD  # attribute rows are padded/inert; unroll over all of them

    kernel = functools.partial(_sfs_sweep_kernel, d=d, block_c=block_c,
                               wcap=wcap, wtile=wtile, sentinel=sentinel)
    vmem = sweep_vmem_bytes(block_c=block_c, wcap=wcap, wtile=wtile,
                            itemsize=jnp.dtype(cands_t.dtype).itemsize)

    def cblk(i, j, nblk_ref):
        # a skipped step revisits the last swept block: no new fetch
        return jnp.minimum(j, jnp.maximum(nblk_ref[i] - 1, 0))

    win_t, count = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the per-partition block bounds sit in scalar memory before
            # the grid starts, where the index maps can read them
            num_scalar_prefetch=1,
            grid=(p, n // block_c),
            in_specs=[
                pl.BlockSpec((D_PAD, block_c),
                             lambda i, j, nb: (i, cblk(i, j, nb))),
                # a squeezed partition dim keeps the block's last two
                # dims (1, BC) legal for any P (the second-minor equals
                # the array's)
                pl.BlockSpec((pl.squeezed, 1, block_c),
                             lambda i, j, nb: (i, 0, cblk(i, j, nb))),
            ],
            out_specs=[
                pl.BlockSpec((D_PAD, wcap), lambda i, j, nb: (i, 0)),
                pl.BlockSpec((pl.squeezed, 1, LANE),
                             lambda i, j, nb: (i, 0, 0)),
            ],
            # the running count is a scalar: it lives in scalar memory
            # and is broadcast into one lane row of the count output per
            # step
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((pd_pad, wcap), cands_t.dtype),
            jax.ShapeDtypeStruct((p, 1, LANE), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(vmem)),
        name="sfs_sweep",
        interpret=interpret,
    )(nblk.astype(jnp.int32), cands_t, mask.reshape(p, 1, n))
    return win_t, count[:, 0, 0]


def sweep_vmem_bytes(*, block_c: int, wcap: int, wtile: int = 0,
                     itemsize: int = 4) -> int:
    """Upper bound on the sweep kernel's scoped VMEM, in bytes.

    Terms, per ``(partition, candidate-block)`` grid step: the resident
    ``(D_PAD, W)`` window block (counted twice, as if double-buffered),
    the pipelined candidate/mask/count blocks, and the intermediates of
    one window test over ``weff`` slots (the whole window untiled, one
    ``wtile`` tile otherwise): each attribute's ``(weff, 1)`` window
    column occupies a full 128-lane row of 32-bit words, and the
    ``(weff, BC)`` / ``(BC, BC)`` test masks are held at 32 bits.  The
    column term is what makes an untiled wide window expensive: Mosaic
    needs 16.2 MiB at W=4096, BC=256 untiled and 1.4 MiB at the same
    window with a 512-slot tile (compiled for a described v5e).  The
    Mosaic compile tests (tests/test_tpu_compile.py) hold the kernel to
    this bound, and the kernel requests it as its VMEM limit when it
    exceeds the compiler's default."""
    weff = wcap if wtile <= 0 else min(wtile, wcap)
    window = 2 * D_PAD * wcap * itemsize
    blocks = 2 * (D_PAD * block_c * itemsize + 8 * block_c * 4) \
        + 2 * 8 * LANE * 4                       # cands, mask, count
    columns = D_PAD * weff * LANE * 4
    tests = 4 * weff * block_c + 8 * block_c * block_c
    return window + blocks + columns + tests


VMEM_DEFAULT = 16 * 2 ** 20   # Mosaic's default scoped VMEM limit (v5e)
VMEM_MAX = 100 * 2 ** 20      # below the 128 MiB of one v5e TensorCore


def vmem_limit_bytes(estimate: int) -> int | None:
    """The scoped-VMEM limit a sweep of this estimate asks Mosaic for:
    the compiler default when the estimate fits under it, else the
    estimate itself.  A geometry past `VMEM_MAX` is a caller error —
    the TPU path tiles wide windows (`repro.kernels.sfs.ops`), so only
    an explicitly pinned huge untiled window gets here."""
    if estimate <= VMEM_DEFAULT:
        return None
    if estimate > VMEM_MAX:
        raise ValueError(
            f"sfs sweep needs ~{estimate} B of VMEM (> {VMEM_MAX}); "
            f"tile the window (wtile)")
    return int(estimate)
