"""GPU (Triton-lowered Pallas) backend for the blocked dominance test.

Same per-tile body as the TPU kernel
(:func:`repro.kernels.dominance.kernel._block_dominated`), different grid
contract: the TPU kernel OR-accumulates over reference blocks in a
*revisited output block*, which relies on the sequential TPU grid — GPU
grid programs are parallel, so that accumulator is not valid there.
This backend launches one program per candidate block (``grid=(C/BC,)``)
and walks the reference blocks in an in-kernel ``fori_loop``, carrying
the OR-reduction in registers; each output tile is written once by
exactly one program.

The reference-block loop bounds resident intermediates at
``block_r x block_c`` test elements — the dominance family's analogue of
the sweep's window tile (its `dominance_vmem_bytes` law is already tile-
shaped, so the Layer-2 verifier gates this backend unchanged).  The
attribute rows are padded to a multiple of ``D_PAD`` rather than capped
at it (per-backend ``max_d`` in `repro.kernels.backend`).  CI validates
the body bitwise in interpret mode (``gpu_interpret``); on a real GPU
runtime the same call compiles through the Triton lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dominance.kernel import D_PAD, _block_dominated

__all__ = ["dominated_mask_pallas_gpu"]


def _dominance_gpu_kernel(cands_ref, refs_ref, mask_ref, out_ref, *,
                          d: int, block_c: int, block_r: int, nrb: int,
                          lower_tri: bool):
    i = pl.program_id(0)
    x = cands_ref[...]  # (d_pad, BC)

    def body(j, acc):
        r = refs_ref[:, pl.ds(j * block_r, block_r)]
        m = mask_ref[:, pl.ds(j * block_r, block_r)]
        return acc | _block_dominated(
            x, r, m, d=d, block_c=block_c, block_r=block_r,
            lower_tri=lower_tri, roff=j * block_r, coff=i * block_c)

    red = jax.lax.fori_loop(0, nrb, body,
                            jnp.zeros((block_c,), jnp.bool_))
    out_ref[...] = red[None, :].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("lower_tri", "block_c", "block_r", "interpret"))
def dominated_mask_pallas_gpu(
    cands_t: jnp.ndarray,
    refs_t: jnp.ndarray,
    ref_mask: jnp.ndarray,
    *,
    lower_tri: bool = False,
    block_c: int = 512,
    block_r: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked dominance-test kernel, one GPU program per candidate block.

    Same contract as
    :func:`repro.kernels.dominance.kernel.dominated_mask_pallas` except
    the attribute row count may be any multiple of ``D_PAD`` (wide d
    pads; extra rows are zero and inert).
    """
    d_pad, c = cands_t.shape
    _, r = refs_t.shape
    assert d_pad % D_PAD == 0, f"attribute rows must pad to {D_PAD}"
    assert refs_t.shape[0] == d_pad, (refs_t.shape, d_pad)
    assert c % block_c == 0 and r % block_r == 0, (c, r, block_c, block_r)

    kernel = functools.partial(
        _dominance_gpu_kernel, d=d_pad, block_c=block_c, block_r=block_r,
        nrb=r // block_r, lower_tri=lower_tri)
    return pl.pallas_call(
        kernel,
        grid=(c // block_c,),
        in_specs=[
            pl.BlockSpec((d_pad, block_c), lambda i: (0, i)),
            pl.BlockSpec((d_pad, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.int32),
        interpret=interpret,
    )(cands_t, refs_t, ref_mask)
