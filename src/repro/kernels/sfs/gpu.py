"""GPU (Triton-lowered Pallas) backend for the fused SFS sweep.

Same kernel body, different grid contract.  The TPU kernel (kernel.py)
relies on the *sequential* TPU grid: the candidate-block index is an
inner grid dimension and the window/count live in revisited output
blocks.  GPU grids are parallel — programs may run in any order and
concurrently — so revisiting an output block across grid steps is not a
valid accumulator there.  This backend therefore launches ONE program
per partition (``grid=(P,)``) and walks the candidate blocks in an
in-kernel ``fori_loop``; the per-partition window/count refs are touched
by exactly one program, so the sequential read-modify-write the sweep
needs is safe.

The per-block step itself is the shared tiled body
(:func:`repro.kernels.sfs.kernel._tiled_block_step`): window test over
``wtile``-column sub-blocks, lower-triangular self-test, scatter-free
integer-bit append — bit-for-bit the TPU kernel's (and the per-pair
reference's) keep decisions, slot assignment and count.  The tiling/VMEM
contract holds unchanged: resident test intermediates are O(wtile x BC)
(``wtile=0`` is normalized to one whole-window tile by the caller), so
`sweep_vmem_bytes` bounds this backend too (read "VMEM" as the GPU's
shared-memory/register budget per program).

The attribute dimension is padded to ``d_pad`` rows (multiple of
``D_PAD``, zero-filled, inert in every comparison) instead of the TPU's
hard ``d <= D_PAD`` sublane cap — the per-backend ``max_d`` lives in the
backend registry (`repro.kernels.backend`).  CI has no GPU, so the
``gpu_interpret`` backend runs this exact body in interpret mode for
bitwise validation; on a real GPU runtime the same call compiles through
the Triton lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sfs.kernel import D_PAD, _tiled_block_step

__all__ = ["sfs_sweep_pallas_gpu"]


def _sfs_sweep_gpu_kernel(cands_ref, mask_ref, win_ref, count_ref, *,
                          d: int, block_c: int, nblocks: int, wcap: int,
                          wtile: int, sentinel):
    win_ref[...] = jnp.full(win_ref.shape, sentinel, win_ref.dtype)

    def cbody(j, count):
        x = cands_ref[:, pl.ds(j * block_c, block_c)]
        xm = mask_ref[:, pl.ds(j * block_c, block_c)][0, :] > 0
        return _tiled_block_step(x, xm, count, win_ref, d=d,
                                 block_c=block_c, wcap=wcap, wtile=wtile)

    count_ref[0, 0] = jax.lax.fori_loop(0, nblocks, cbody, jnp.int32(0))


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "wcap", "wtile", "sentinel", "interpret"))
def sfs_sweep_pallas_gpu(
    cands_t: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    block_c: int,
    wcap: int,
    sentinel: float,
    wtile: int = 0,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused SFS sweep, one GPU program per partition.

    Same contract as :func:`repro.kernels.sfs.kernel.sfs_sweep_pallas`
    except the attribute row count of ``cands_t`` may be any multiple of
    ``D_PAD`` (wide d pads to the next multiple; extra rows are zero and
    inert).  ``wtile=0`` runs one whole-window tile.
    """
    pd_pad, n = cands_t.shape
    p = mask.shape[0]
    assert p > 0 and pd_pad % p == 0, (pd_pad, p)
    d_pad = pd_pad // p
    assert d_pad % D_PAD == 0, d_pad
    assert mask.shape == (p, n), (mask.shape, p, n)
    assert n % block_c == 0, (n, block_c)
    wtile = wtile or wcap   # the GPU body is always the tiled step
    assert wcap % wtile == 0, (wcap, wtile)

    kernel = functools.partial(
        _sfs_sweep_gpu_kernel, d=d_pad, block_c=block_c,
        nblocks=n // block_c, wcap=wcap, wtile=wtile, sentinel=sentinel)
    win_t, count = pl.pallas_call(
        kernel,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((d_pad, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_pad, wcap), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pd_pad, wcap), cands_t.dtype),
            jax.ShapeDtypeStruct((p, 1), jnp.int32),
        ],
        interpret=interpret,
    )(cands_t, mask)
    return win_t, count[:, 0]
