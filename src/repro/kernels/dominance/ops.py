"""Jit'd public wrapper around the blocked dominance kernel.

This is the ONE call for pairwise dominance between two (possibly
different) point sets — pre-filter, eviction, NoSeq relative skylines,
representative filtering all route through it.  The local-phase SFS scan
does NOT: that is the fused sweep's job (``repro.kernels.sfs.sfs_sweep``,
one dispatch per partition batch).  Backend selection normally happens one
layer up (``repro.kernels.backend.resolve_spec(cfg.impl).dominance``);
the ``impl`` accepted here is the per-family string:

  * ``impl='pallas'``     — compiled Pallas TPU kernel (the production path).
  * ``impl='interpret'``  — same kernel body, interpret mode (CPU validation).
  * ``impl='gpu'``        — Triton-lowered Pallas kernel (gpu.py): one
                            program per candidate block, ref blocks
                            walked in-kernel (GPU grids are parallel).
  * ``impl='gpu_interpret'`` — the GPU body in interpret mode.
  * ``impl='jnp'``        — blocked pure-jnp fallback (fast on XLA:CPU).
  * ``impl='auto'``       — 'pallas' on TPU backends, 'gpu' on GPU
                            backends, 'jnp' elsewhere.

The attribute-width cap is per-implementation data
(`repro.kernels.backend.impl_max_d`): the TPU sublane layout caps at
d <= 8, the GPU layout pads attribute rows instead, and the jnp path
takes any d.

All paths implement the contract of :func:`ref.dominated_mask_ref` and are
tested against it (tests/test_dominance_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dominance import kernel as _kernel
from repro.kernels.dominance import ref as _ref

__all__ = ["dominated_mask"]

# refs-block size for the memory-bounded jnp path: bounds the (C, BR, d)
# broadcast intermediate.
_JNP_REF_BLOCK = 2048


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _dominated_mask_jnp(cands, refs, ref_mask, lower_tri):
    """Blocked pure-jnp path: loop over reference blocks, OR-accumulate."""
    c, d = cands.shape
    r = refs.shape[0]
    if r <= _JNP_REF_BLOCK:
        return _ref.dominated_mask_ref(cands, refs, ref_mask,
                                       lower_tri=lower_tri)

    rp = _ceil_to(r, _JNP_REF_BLOCK)
    refs_p = jnp.pad(refs, ((0, rp - r), (0, 0)))
    mask_p = jnp.pad(ref_mask, (0, rp - r))
    nb = rp // _JNP_REF_BLOCK
    cand_idx = jnp.arange(c)

    def body(b, acc):
        off = b * _JNP_REF_BLOCK
        rblk = jax.lax.dynamic_slice_in_dim(refs_p, off, _JNP_REF_BLOCK, 0)
        mblk = jax.lax.dynamic_slice_in_dim(mask_p, off, _JNP_REF_BLOCK, 0)
        le = jnp.all(rblk[:, None, :] <= cands[None, :, :], axis=-1)
        lt = jnp.any(rblk[:, None, :] < cands[None, :, :], axis=-1)
        dom = le & lt & mblk[:, None]
        if lower_tri:
            rid = off + jnp.arange(_JNP_REF_BLOCK)
            dom = dom & (rid[:, None] < cand_idx[None, :])
        return acc | jnp.any(dom, axis=0)

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((c,), jnp.bool_))


def _dominated_mask_pallas(cands, refs, ref_mask, lower_tri, block_c,
                           block_r, interpret, gpu=False, *, name):
    c, d = cands.shape
    r = refs.shape[0]
    cp = _ceil_to(max(c, 1), block_c)
    rp = _ceil_to(max(r, 1), block_r)
    # the GPU layout pads the attribute rows to a multiple of the
    # sublane tile instead of capping at it
    d_pad = _ceil_to(max(d, 1), _kernel.D_PAD) if gpu else _kernel.D_PAD
    # Transposed layout with zero-padded attribute rows: 0 <= 0 keeps `le`
    # true and 0 < 0 keeps `lt` false, so padded attributes are inert.
    cands_t = jnp.zeros((d_pad, cp), cands.dtype)
    cands_t = cands_t.at[:d, :c].set(cands.T)
    refs_t = jnp.zeros((d_pad, rp), refs.dtype)
    refs_t = refs_t.at[:d, :r].set(refs.T)
    mask2d = jnp.zeros((1, rp), jnp.int32)
    mask2d = mask2d.at[0, :r].set(ref_mask.astype(jnp.int32))
    if gpu:
        from repro.kernels.dominance import gpu as _gpu
        out = _gpu.dominated_mask_pallas_gpu(
            cands_t, refs_t, mask2d, lower_tri=lower_tri, block_c=block_c,
            block_r=block_r, interpret=interpret)
    else:
        out = _kernel.dominated_mask_pallas(
            cands_t, refs_t, mask2d, lower_tri=lower_tri, block_c=block_c,
            block_r=block_r, interpret=interpret, name=name)
    return out[0, :c] > 0


@functools.partial(
    jax.jit,
    static_argnames=("lower_tri", "impl", "block_c", "block_r", "name"))
def dominated_mask(
    cands: jnp.ndarray,
    refs: jnp.ndarray,
    ref_mask: jnp.ndarray | None = None,
    *,
    lower_tri: bool = False,
    impl: str = "auto",
    block_c: int = 512,
    block_r: int = 512,
    name: str = "dominated_mask",
) -> jnp.ndarray:
    """(C,) bool: for each candidate, is it dominated by a valid ref?

    See ref.dominated_mask_ref for exact semantics.  ``name`` names the
    TPU kernel (the op a device trace shows); the jnp path has no kernel
    to name.
    """
    if cands.ndim != 2 or refs.ndim != 2:
        raise ValueError("cands/refs must be (N, d)")
    if ref_mask is None:
        ref_mask = jnp.ones((refs.shape[0],), jnp.bool_)
    if impl == "auto":
        backend = jax.default_backend()
        impl = {"tpu": "pallas", "gpu": "gpu"}.get(backend, "jnp")
    if impl == "jnp":
        # the jnp path has no attribute-padding layout, so any d works
        return _dominated_mask_jnp(cands, refs, ref_mask, lower_tri)
    if impl in ("pallas", "interpret", "gpu", "gpu_interpret"):
        # attribute-width caps are per-backend data — enforced after
        # impl resolution so wide-d inputs keep working on capless paths
        from repro.kernels.backend import impl_max_d
        cap = impl_max_d(impl)
        if cap is not None and cands.shape[1] > cap:
            raise ValueError(
                f"d > {cap} not supported by the Pallas kernel; "
                f"use impl='jnp'")
        return _dominated_mask_pallas(
            cands, refs, ref_mask, lower_tri, block_c, block_r,
            interpret=impl in ("interpret", "gpu_interpret"),
            gpu=impl in ("gpu", "gpu_interpret"), name=name)
    raise ValueError(f"unknown impl {impl!r}")
