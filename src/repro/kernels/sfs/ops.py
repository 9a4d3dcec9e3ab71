"""One-call entry for the fused local-phase SFS sweep.

:func:`sfs_sweep` runs the entire sorted Sort-Filter-Skyline scan for a
**batch of partitions** in one dispatch.  The contract (shared by every
implementation and property-tested bit-for-bit in
tests/test_sfs_kernel.py):

  inputs   (P, npad, d) partitions, each presorted by a strictly monotone
           score (SFS topological order) with invalid rows holding the
           sentinel coordinate, plus the (P, npad) validity mask;
           ``npad % block == 0``.
  output   per partition: the packed window holding the first ``wcap``
           skyline members in score order, its validity mask, and the
           total keep count (may exceed ``wcap`` — overflow drops extra
           tuples, never adds spurious ones).

Implementations (selected by the backend layer, repro.kernels.backend):

  * ``'pallas'``     — compiled Pallas TPU kernel (kernel.py): one grid
                       over (partition, candidate-block), window + count
                       resident on chip for the whole scan.
  * ``'interpret'``  — same kernel body, interpret mode (CPU validation).
  * ``'gpu'``        — Triton-lowered Pallas kernel (gpu.py): one program
                       per partition, candidate blocks walked in-kernel
                       (GPU grids are parallel, so the TPU's revisited-
                       output-block residency trick does not apply).
  * ``'gpu_interpret'`` — the GPU body in interpret mode (CI validation).
  * ``'jnp'``        — the single-dispatch blocked-jnp sweep below: ONE
                       ``lax.scan`` whose body fuses the window test,
                       the lower-triangular self-test and the append
                       into a single combined comparison per block,
                       vmapped over partitions.  Replaces the seed's
                       per-(window-block, candidate-block) dominance
                       kernel launches.
  * ``'perpair'``    — the seed per-pair scan (ref.py), kept as the
                       bit-for-bit oracle and benchmark baseline.

All implementations take a ``wtile`` window-tile width: 0 tests the
whole window per candidate block (resident O(wcap x block)); a divisor
of ``wcap`` iterates the test over wtile-row sub-blocks so the resident
footprint is O(wtile x block) at any capacity.  The tile only changes
the schedule — every (impl, wtile) pair is bit-for-bit identical and
property-tested against the per-pair reference.  The per-pair reference
itself ignores ``wtile`` (it is the tile-free oracle).

Sorting/padding lives one layer up (repro.core.sfs.local_skyline_batch),
so all implementations consume identical bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import KernelSpec, resolve_spec
from repro.kernels.sfs import kernel as _kernel
from repro.kernels.sfs import ref as _ref

__all__ = ["sfs_sweep", "sweep_blocks", "tpu_geometry", "traced_geometries",
           "TPU_WTILE"]


def _sweep_one_jnp(pts_s, mask_s, *, block: int, wcap: int, sentinel,
                   wtile: int = 0):
    """Fused jnp sweep of ONE sorted partition.

    One ``lax.scan`` whose body fuses the whole per-block step the
    per-pair reference spreads over many kernel dispatches:

      * the lower-triangular self-test and the test against the *first*
        window block are ONE combined comparison — the refs are
        ``concat(window[:block], x)`` under a single STATIC allow mask
        (all-true on the window rows, lower-triangular on the self
        rows).  The first window block is resident in the scan carry, so
        the common case (running skyline <= one block) runs a single
        fused comparison per step with no dynamic slicing and no
        per-pair dispatch plumbing;
      * no runtime validity masks are built or applied in the dominance
        tests at all: every invalid ref row — empty window slot, masked
        or padded candidate — holds the sentinel coordinate in all
        attributes by construction of this entry point, and a sentinel
        row cannot dominate data whose coordinates stay below the
        sentinel (1.7e38), so those rows are inert without masking.
        This removes ~2 * block^2 bools of mask traffic per step;
      * only the rare deeper window blocks (running skyline past
        ``block`` rows) take the inner dynamically-bounded loop, with
        the same work bound as the reference.

    With ``wtile > 0`` the scan body instead iterates the window test
    over wtile-row sub-blocks (self-test separate, no resident first
    window block), bounding every materialized comparison at
    O(wtile x block) elements — the jnp twin of the Pallas kernel's
    `_tiled_block_step`, for hosts where the untiled fused comparison
    would blow the XLA:CPU/GPU working set at huge capacities.

    Keep decisions are boolean-identical, so the output is bit-for-bit
    the per-pair reference's (including overflow behaviour).
    """
    npad, d = pts_s.shape
    nb = npad // block
    xs = pts_s.reshape(nb, block, d)
    xms = mask_s.reshape(nb, block)
    tri = (jnp.arange(block)[:, None] < jnp.arange(block)[None, :])
    # static: window rows always allowed (empty slots are sentinel-inert),
    # self rows only from strictly earlier (smaller-score) positions
    allow = jnp.concatenate([jnp.ones((block, block), jnp.bool_), tri])
    nwb_max = wcap // block

    window0 = jnp.full((wcap, d), sentinel, pts_s.dtype)
    wmask0 = jnp.zeros((wcap,), jnp.bool_)

    def append(window, wmask, wcount, x, keep):
        pos = wcount + jnp.cumsum(keep) - 1
        dest = jnp.where(keep & (pos < wcap), pos, wcap)
        window = window.at[dest].set(x, mode="drop")
        wmask = wmask.at[dest].set(True, mode="drop")
        return window, wmask, wcount + jnp.sum(keep)

    if nb == 1:
        # Single-block fast path (small inputs, the serving regime): the
        # window is empty, so the self-test alone decides membership
        # (invalid rows are sentinel-filled, hence inert as refs) — the
        # window tile is irrelevant here.
        x, xm = xs[0], xms[0]
        le = jnp.all(x[:, None, :] <= x[None, :, :], axis=-1)
        lt = jnp.any(x[:, None, :] < x[None, :, :], axis=-1)
        domin = jnp.any(le & lt & tri, axis=0)
        window, wmask, wcount = append(window0, wmask0, jnp.int32(0), x,
                                       xm & ~domin)
        return window, wmask, wcount.astype(jnp.int32)

    if wtile:
        # Window-tiled scan body: self-test separate, window test over
        # wtile-row sub-blocks of the LIVE window only (slots past the
        # count hold the sentinel and are inert, so any tile bound >=
        # live is exact — live is just the work bound).
        ntiles = wcap // wtile

        def tbody(carry, inp):
            window, wmask, wcount = carry
            x, xm = inp
            le = jnp.all(x[:, None, :] <= x[None, :, :], axis=-1)
            lt = jnp.any(x[:, None, :] < x[None, :, :], axis=-1)
            dom = jnp.any(le & lt & tri, axis=0)
            live = jnp.minimum(
                (jnp.minimum(wcount, wcap) + wtile - 1) // wtile, ntiles)

            def wbody(t, acc):
                wblk = jax.lax.dynamic_slice(window, (t * wtile, 0),
                                             (wtile, d))
                wle = jnp.all(wblk[:, None, :] <= x[None, :, :], axis=-1)
                wlt = jnp.any(wblk[:, None, :] < x[None, :, :], axis=-1)
                return acc | jnp.any(wle & wlt, axis=0)

            dom = jax.lax.fori_loop(0, live, wbody, dom)
            window, wmask, wcount = append(window, wmask, wcount, x,
                                           xm & ~dom)
            return (window, wmask, wcount), None

        (window, wmask, wcount), _ = jax.lax.scan(
            tbody, (window0, wmask0, jnp.int32(0)), (xs, xms))
        return window, wmask, wcount

    def body(carry, inp):
        window, wmask, wcount = carry
        x, xm = inp

        # (a)+(b) fused: dominated by the first window block OR by an
        # earlier (smaller-score) row of the own block — one comparison
        # under the static allow mask.  Testing window block 0
        # unconditionally is exact even before anything was appended:
        # empty slots hold the sentinel and cannot dominate.
        refs = jnp.concatenate([window[:block], x])
        le = jnp.all(refs[:, None, :] <= x[None, :, :], axis=-1)
        lt = jnp.any(refs[:, None, :] < x[None, :, :], axis=-1)
        dom = jnp.any(le & lt & allow, axis=0)

        # deeper active window blocks (running skyline > block rows):
        # same dynamic work bound as the reference
        nwb = jnp.minimum((wcount + block - 1) // block, nwb_max)

        def wbody(wb, acc):
            wblk = jax.lax.dynamic_slice(window, (wb * block, 0),
                                         (block, d))
            wle = jnp.all(wblk[:, None, :] <= x[None, :, :], axis=-1)
            wlt = jnp.any(wblk[:, None, :] < x[None, :, :], axis=-1)
            return acc | jnp.any(wle & wlt, axis=0)

        dom = jax.lax.fori_loop(1, jnp.maximum(nwb, 1), wbody, dom)
        # (c) append, in the same scan body
        window, wmask, wcount = append(window, wmask, wcount, x,
                                       xm & ~dom)
        return (window, wmask, wcount), None

    (window, wmask, wcount), _ = jax.lax.scan(
        body, (window0, wmask0, jnp.int32(0)), (xs, xms))
    return window, wmask, wcount


def _pack_transposed(pts_s, d_pad):
    """(P, npad, d) -> (P * d_pad, npad) transposed layout with zero-
    padded attribute rows: 0 <= 0 keeps `le` true and 0 < 0 keeps `lt`
    false, so padded attributes are inert in every comparison."""
    p, npad, d = pts_s.shape
    cands_t = jnp.zeros((p, d_pad, npad), pts_s.dtype)
    cands_t = cands_t.at[:, :d, :].set(jnp.swapaxes(pts_s, 1, 2))
    return cands_t.reshape(p * d_pad, npad)


# window tile of the compiled TPU sweep for windows wider than one tile
TPU_WTILE = 512


def tpu_geometry(block: int, npad: int, wcap: int, wtile: int,
                 ) -> tuple[int, int]:
    """``(candidate block, window tile)`` the compiled TPU sweep runs for
    a requested geometry — derived from the shapes alone.

    Mosaic wants the last dim of every block to be a multiple of the
    128-lane tile or the whole array, and dynamic window slices to
    start on a lane multiple.  So a candidate block that is neither is
    rounded up to the lane tile (the caller pads the batch with inert
    sentinel rows), and a window wider than `TPU_WTILE` is always
    tested in lane-aligned tiles: untiled, the ``(W, 1)`` window columns
    alone take ~4 KiB of VMEM per slot (`sweep_vmem_bytes`), and every
    step would test all W slots rather than the live ones.  A requested
    tile is kept when it is a lane-aligned divisor of the window.  A
    window that is not a lane multiple (blocks below 128 rows, i.e.
    tiny inputs) stays untiled.  Candidate block and tile are pure
    schedule: every geometry is bit-identical."""
    lane = _kernel.LANE
    bc = block if block % lane == 0 or block == npad else _ceil_to(block,
                                                                   lane)
    if wcap <= TPU_WTILE or wcap % lane:
        return bc, 0
    t = wtile if 0 < wtile < wcap and wtile % lane == 0 else TPU_WTILE
    while wcap % t:
        t -= lane
    return bc, t


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# trace-time record of the compiled sweep geometries (one entry per
# trace, like `repro.core.parallel.trace_count`), so a run can report
# which tiling its programs actually compiled
_GEOMETRIES: list[dict] = []


def traced_geometries() -> list[dict]:
    """Every Pallas sweep geometry traced so far, oldest first."""
    return list(_GEOMETRIES)


def sweep_blocks(mask_s: jnp.ndarray, block: int) -> jnp.ndarray:
    """(..., P) int32: the candidate blocks the Pallas sweep runs on each
    partition of a (..., P, npad) validity mask — up to and including the
    last block that holds a valid row, 0 for a partition with none.  Read
    from the mask itself, so it is exact whatever the order of the rows;
    the blocks after it keep nothing."""
    npad = mask_s.shape[-1]
    last = jnp.max(jnp.where(mask_s, jnp.arange(1, npad + 1, dtype=jnp.int32),
                             0), axis=-1)
    return (last + block - 1) // block


def _sweep_pallas(pts_s, mask_s, *, block: int, wcap: int, wtile: int,
                  sentinel, interpret: bool):
    """Pack the sorted batch into the TPU kernel's transposed layout,
    run the one-grid sweep up to each partition's last valid block
    (`sweep_blocks`), and unpack.  Compiled (not interpret), the
    geometry is `tpu_geometry`'s; interpret mode runs the requested one
    so CPU tests can exercise any tile."""
    p, npad, d = pts_s.shape
    if d > _kernel.D_PAD:
        raise ValueError(
            f"d={d} > {_kernel.D_PAD} not supported by the Pallas sweep; "
            f"use impl='jnp'")
    if not interpret:
        block, wtile = tpu_geometry(block, npad, wcap, wtile)
        if npad % block:  # pad with inert rows to whole candidate blocks
            extra = _ceil_to(npad, block) - npad
            pts_s = jnp.pad(pts_s, ((0, 0), (0, extra), (0, 0)),
                            constant_values=sentinel)
            mask_s = jnp.pad(mask_s, ((0, 0), (0, extra)))
    _GEOMETRIES.append(dict(
        p=p, n=pts_s.shape[1], d=d, block=block, wcap=wcap, wtile=wtile,
        interpret=interpret,
        vmem_limit=_kernel.vmem_limit_bytes(_kernel.sweep_vmem_bytes(
            block_c=block, wcap=wcap, wtile=wtile,
            itemsize=jnp.dtype(pts_s.dtype).itemsize))))

    @jax.custom_batching.custom_vmap
    def sweep(pts_s, mask_s):
        p = pts_s.shape[0]
        cands_t = _pack_transposed(pts_s, _kernel.D_PAD)
        win_t, count = _kernel.sfs_sweep_pallas(
            cands_t, mask_s.astype(jnp.int32), sweep_blocks(mask_s, block),
            block_c=block, wcap=wcap, wtile=wtile,
            sentinel=float(sentinel), interpret=interpret)
        window = jnp.swapaxes(
            win_t.reshape(p, _kernel.D_PAD, wcap)[:, :d, :], 1, 2)
        return window, _packed_mask(count, wcap), count

    @sweep.def_vmap
    def _fold(q, batched, pts_s, mask_s):
        # partitions are independent: a batch of Q (P, npad) sweeps is
        # one (Q * P, npad) sweep, so vmap keeps a single launch (Pallas
        # would otherwise loop over the batch, one launch per query,
        # since the prefetched block bounds are batched)
        pts_s, mask_s = (x if b else jnp.broadcast_to(x, (q, *x.shape))
                         for x, b in zip((pts_s, mask_s), batched))
        outs = sweep(pts_s.reshape(-1, *pts_s.shape[2:]),
                     mask_s.reshape(-1, mask_s.shape[-1]))
        return (tuple(o.reshape(q, -1, *o.shape[1:]) for o in outs),
                (True, True, True))

    return sweep(pts_s, mask_s)


def _packed_mask(count, wcap: int):
    """(P, wcap) validity of a packed window: the sweep fills slots in
    keep order from 0, so exactly the first min(count, wcap) are valid."""
    return jnp.arange(wcap, dtype=jnp.int32)[None, :] < count[:, None]


def _sweep_gpu(pts_s, mask_s, *, block: int, wcap: int, wtile: int,
               sentinel, interpret: bool):
    """Pack for the GPU kernel (attribute rows padded to a multiple of
    D_PAD — no hard d cap), run one program per partition, unpack."""
    from repro.kernels.sfs import gpu as _gpu
    p, npad, d = pts_s.shape
    d_pad = -(-max(d, 1) // _kernel.D_PAD) * _kernel.D_PAD
    cands_t = _pack_transposed(pts_s, d_pad)
    mask2d = mask_s.astype(jnp.int32)
    win_t, count = _gpu.sfs_sweep_pallas_gpu(
        cands_t, mask2d, block_c=block, wcap=wcap, wtile=wtile,
        sentinel=float(sentinel), interpret=interpret)
    window = jnp.swapaxes(win_t.reshape(p, d_pad, wcap)[:, :d, :], 1, 2)
    return window, _packed_mask(count, wcap), count


def _normalize_wtile(wtile: int, wcap: int, block: int) -> int:
    """Static window-tile normalization, shared by every implementation:
    <= 0 means untiled; tiles are clamped to the window and must divide
    it — a non-divisor falls back to ``block`` (which divides ``wcap``
    by construction in every caller), or to untiled as the last resort.
    Any returned value is bit-identical to any other (the tile is pure
    schedule), so normalizing is always safe."""
    wtile = int(wtile)
    if wtile <= 0:
        return 0
    if wtile >= wcap:
        return wcap
    if wcap % wtile != 0:
        return block if wcap % block == 0 else 0
    return wtile


@functools.partial(
    jax.jit, static_argnames=("block", "wcap", "wtile", "sentinel", "spec"))
def sfs_sweep(
    pts_s: jnp.ndarray,
    mask_s: jnp.ndarray,
    *,
    block: int,
    wcap: int,
    sentinel: float,
    wtile: int = 0,
    spec: KernelSpec | str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused local-phase SFS sweep of a (P, npad, d) sorted batch.

    ``wtile`` is the window-tile width (0 = whole window resident; see
    the module docstring).  Returns ``(window (P, wcap, d), wmask
    (P, wcap) bool, count (P,) int32)``; see the module docstring for
    the contract.
    """
    if pts_s.ndim != 3 or mask_s.shape != pts_s.shape[:2]:
        raise ValueError(f"expected (P, npad, d)/(P, npad), got "
                         f"{pts_s.shape}/{mask_s.shape}")
    if pts_s.shape[1] % block != 0:
        raise ValueError(f"npad={pts_s.shape[1]} not a multiple of "
                         f"block={block}")
    spec = resolve_spec(spec)
    d = pts_s.shape[2]
    if spec.max_d is not None and d > spec.max_d:
        raise ValueError(
            f"d={d} > {spec.max_d} not supported by the {spec.name!r} "
            f"backend; use impl='jnp'")
    wtile = _normalize_wtile(wtile, wcap, block)
    if spec.sweep in ("pallas", "interpret"):
        return _sweep_pallas(pts_s, mask_s, block=block, wcap=wcap,
                             wtile=wtile, sentinel=sentinel,
                             interpret=spec.sweep == "interpret")
    if spec.sweep in ("gpu", "gpu_interpret"):
        return _sweep_gpu(pts_s, mask_s, block=block, wcap=wcap,
                          wtile=wtile, sentinel=sentinel,
                          interpret=spec.sweep == "gpu_interpret")
    if spec.sweep == "jnp":
        one = functools.partial(_sweep_one_jnp, block=block, wcap=wcap,
                                wtile=wtile, sentinel=sentinel)
    else:  # 'perpair' — the seed reference path (tile-free oracle)
        one = functools.partial(_ref.sfs_sweep_perpair, block=block,
                                wcap=wcap, sentinel=sentinel,
                                dominance_impl=spec.dominance)
    return jax.vmap(one)(pts_s, mask_s)
