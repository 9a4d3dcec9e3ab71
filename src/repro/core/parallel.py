"""Parallel skyline computation (paper Algorithm 2) on a JAX device mesh.

The three phases map onto SPMD as (DESIGN.md §3):

  partition  — partition-id map + `bucketize` routing (global data prep,
               the analogue of Spark's shuffle),
  local      — per-partition block-SFS: ONE fused-sweep dispatch for the
               whole partition batch a device owns
               (`repro.core.sfs.local_skyline_batch` -> the kernel
               backend's sfs sweep), `shard_map` over the `workers` axis,
  merge      — either the paper's sequential pass (gather + one more
               fused-sweep call on the compacted union) or NoSeq
               (all_gather of the local skylines + per-worker
               relative-skyline filtering against pd_i).

Representative Filtering (paper §4.1) selects k representatives per
partition, all_gathers them, removes dominated representatives, and
pre-filters every partition before local skyline computation.

Execution model: all three phases run as **one jitted SPMD program**
(`fused_skyline_fn`). Partitioning and routing are traced into the same
computation as the shard_mapped local+merge phases, with
`with_sharding_constraint` handing the routed buckets to the `workers`
mesh axis — there is no host round-trip or `device_put` between stages,
and the returned stats pytree stays on device until the caller reads it.
Compiled programs are cached per (cfg, mesh, axis_name); jit's own cache
handles shapes, so repeated same-shape queries never retrace (observable
via `trace_count()`).

A single-device semantic mode (mesh=None) runs the identical math with
plain vmaps — used by unit tests, the batched multi-query engine
(`repro.serve.engine`, which vmaps this program over queries), and CPU
benchmarks.

For engine batches of *large* queries there is additionally a 2-D
(queries x workers) program (`fused_skyline_batch_fn` with a mesh): the
query batch is sharded over a `queries` mesh axis and, within each query
shard, every query's partitions are sharded over the `workers` axis —
the distributed-skyline regime of Zhang & Zhang combined with query
batching. Axis names are parameters throughout, so the same program
embeds in larger meshes.

Both one-shot programs are thin wrappers over the device-resident
`SkylineState` abstraction of `repro.core.incremental` ("insert
everything into an empty state"); streaming callers keep the state
between chunks instead of discarding it.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import filtering, noseq, partition
from repro.core.dominance import apply_sentinel, canonical_order
from repro.core.sfs import (SkyBuffer, block_sfs, compact, compact_order,
                            local_skyline_batch)
from repro.kernels.backend import resolve_spec

__all__ = ["SkyConfig", "parallel_skyline", "fused_skyline_fn",
           "fused_skyline_batch_fn", "effective_parts", "partition_stage",
           "local_stage", "merge_stage", "merge_rounds", "resolve_merge",
           "trace_count", "STAGE_SCOPES", "DISPATCH_SPAN"]

# Names of the pipeline's stages in the compiled program. Each stage runs
# under a `jax.named_scope` of its name, so every op the program traces
# carries exactly one of them in its HLO ``op_name`` metadata (and nothing
# else of the compiled program changes). A profiler trace attributes
# device time to stages by these names.
PARTITION_SCOPE = "sky.partition"    # part ids, routing, hand-off to workers
REP_FILTER_SCOPE = "sky.rep_filter"  # representative filtering (paper §4.1)
LOCAL_SCOPE = "sky.local"            # per-partition skylines
MERGE_SCOPE = "sky.merge"            # union, final sweep, canonical order
STAGE_SCOPES = (PARTITION_SCOPE, REP_FILTER_SCOPE, LOCAL_SCOPE, MERGE_SCOPE)
# host span (`jax.profiler.TraceAnnotation`) around each one-shot dispatch
DISPATCH_SPAN = "sky.dispatch"


@dataclasses.dataclass(frozen=True)
class SkyConfig:
    """Configuration of the parallel skyline pipeline."""
    strategy: str = "sliced"      # random | grid | angular | sliced
    p: int = 8                    # target #partitions (grid/angular: derived)
    m: int = 0                    # slices/dim (grid/angular); 0 = derive from p
    bucket_factor: float = 1.0    # bucket capacity = factor * ceil(n/p)
    bucket_capacity: int = 0      # explicit override (0 = use factor)
    local_capacity: int = 0       # phase-1 window capacity (0 = bucket cap)
    capacity: int = 4096          # final skyline buffer capacity
    block: int = 256              # dominance-test block size
    wtile: int = 0                # sweep window tile (0 = whole window)
    rep_filter: str | None = None  # None | sorted | region | random
    rep_k: int = 16               # representatives per partition
    noseq: bool = False           # parallel phase 2 (paper §4.2)
    grid_filter: bool = True      # grid-only pre-filter (paper §3.2)
    sliced_dim: int = 0
    impl: str = "auto"            # dominance kernel impl
    merge: str = "flat"           # union merge topology: flat | tree | auto
    donate: bool = True           # donate state/arena operands (in-place
    #                               updates; off = A/B copy semantics)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def effective_parts(cfg: SkyConfig, d: int) -> tuple[int, int]:
    """(p, m) actually used, honouring grid/angular constraints."""
    if cfg.strategy == "grid":
        m = cfg.m or partition.slices_for_target_parts(cfg.p, d)
        return partition.grid_num_parts(m, d), m
    if cfg.strategy == "angular":
        m = cfg.m or partition.slices_for_target_parts(cfg.p, max(d - 1, 1))
        return partition.angular_num_parts(m, d), m
    return cfg.p, 0


def _grid_cells(p: int, m: int, d: int) -> jnp.ndarray:
    """(p, d) cell coordinates of each grid partition index."""
    i = jnp.arange(p, dtype=jnp.int32)
    return jnp.stack([(i // (m ** k)) % m for k in range(d)], axis=1)


# --------------------------------------------------------------------------
# Stage 1: partition (global data prep)
# --------------------------------------------------------------------------

def partition_stage(pts: jnp.ndarray, mask: jnp.ndarray | None,
                    cfg: SkyConfig, key: jax.Array | None = None):
    """Partition-id map + routing into (p, C, d) buckets + meta."""
    n, d = pts.shape
    if mask is None:
        mask = jnp.ones((n,), jnp.bool_)
    if key is None:
        key = jax.random.PRNGKey(0)
    p, m = effective_parts(cfg, d)

    stats: dict[str, Any] = {}
    cells = jnp.zeros((p, d), jnp.int32)
    if cfg.strategy == "random":
        ids = partition.random_part_ids(key, n, p)
    elif cfg.strategy == "sliced":
        ids = partition.sliced_part_ids(pts, mask, p, cfg.sliced_dim)
    elif cfg.strategy == "grid":
        if cfg.grid_filter:
            gf = filtering.grid_filter(pts, mask, m)
            mask = gf.mask
            stats["grid_filter_dropped"] = gf.dropped
        ids = partition.grid_part_ids(pts, m)
        cells = _grid_cells(p, m, d)
    elif cfg.strategy == "angular":
        ids = partition.angular_part_ids(pts, m)
    else:
        raise ValueError(f"unknown strategy {cfg.strategy!r}")

    cap = cfg.bucket_capacity or max(
        1, int(cfg.bucket_factor * _ceil_div(n, p)) + 1)
    buckets = partition.bucketize(pts, mask, ids, p, cap)
    meta = {"p": p, "m": m, "cells": cells,
            "part_idx": jnp.arange(p, dtype=jnp.int32)}
    stats["bucket_counts"] = buckets.counts
    stats["bucket_overflow"] = buckets.overflow
    stats["n_valid"] = jnp.sum(mask)
    return buckets, meta, stats


# --------------------------------------------------------------------------
# Stage 2: local skylines (+ representative filtering), per worker
# --------------------------------------------------------------------------

def _select_local_reps(bufs, bmask, cfg: SkyConfig, key):
    keys = jax.random.split(key, bufs.shape[0])
    dom_impl = resolve_spec(cfg.impl).dominance
    def one(b, m, k):
        return filtering.select_representatives(
            b, m, cfg.rep_k, strategy=cfg.rep_filter, key=k, impl=dom_impl)
    return jax.vmap(one)(bufs, bmask, keys)


def local_stage(bufs, bmask, cfg: SkyConfig, *, key=None, gather=None):
    """Phase 1 on the partitions held by this worker.

    `gather` concatenates along axis 0 across workers (identity on a single
    device, lax.all_gather(tiled) under shard_map)."""
    if gather is None:
        gather = lambda x: x
    if key is None:
        key = jax.random.PRNGKey(1)
    p_local, cap, d = bufs.shape
    stats: dict[str, Any] = {}

    if cfg.rep_filter:
        with jax.named_scope(REP_FILTER_SCOPE):
            dom_impl = resolve_spec(cfg.impl).dominance
            reps, rmask = _select_local_reps(bufs, bmask, cfg, key)
            pool = gather(reps).reshape(-1, d)
            pmask = gather(rmask).reshape(-1)
            # drop dominated representatives before sharing (paper §4.1)
            pmask = pmask & ~jax.vmap(
                lambda t: jnp.any((jnp.all(pool <= t, -1) &
                                   jnp.any(pool < t, -1)) & pmask))(pool)
            before = jnp.sum(bmask)
            bmask = jax.vmap(
                lambda b, m: filtering.filter_by_representatives(
                    b, m, pool, pmask, impl=dom_impl))(bufs, bmask)
            stats["rep_filter_dropped"] = before - jnp.sum(bmask)

    # Phase 1 proper: the whole partition batch through ONE fused-sweep
    # dispatch (window test + self-test + append fused; no per-pair
    # dominance launches — see repro.kernels.sfs).
    with jax.named_scope(LOCAL_SCOPE):
        local_cap = cfg.local_capacity or cap
        sky = local_skyline_batch(bufs, bmask, capacity=local_cap,
                                  block=cfg.block, impl=cfg.impl,
                                  wtile=cfg.wtile)
        stats["local_sizes"] = sky.count
        stats["local_overflow"] = jnp.any(sky.overflow)
        # candidate blocks the sweep runs (valid rows sort first), against
        # p x npad / block padded
        stats["sweep_blocks"] = jnp.sum(-(-jnp.sum(bmask, -1) // cfg.block))
    return sky, stats


# --------------------------------------------------------------------------
# Stage 3: merge — sequential (paper Alg. 2 line 5) or NoSeq (paper §4.2),
# over one of two collective topologies: the flat all_gather union or the
# ⌈log₂(W)⌉-round pruning ppermute tree (`SkyConfig.merge`)
# --------------------------------------------------------------------------

def merge_rounds(axis_size: int) -> int:
    """⌈log₂(axis_size)⌉ — the tree merge's ppermute round count."""
    return max(int(axis_size) - 1, 0).bit_length()


def resolve_merge(cfg: SkyConfig, *, axis_size=None, p_total=None,
                  local_cap=None, d=None) -> str:
    """The single merge-topology decision point, shared by every
    execution path (one-shot, incremental insert, windowed head-epoch
    insert, the engine programs).

    ``'flat'`` / ``'tree'`` are honoured as-is; ``'auto'`` compares the
    modeled per-worker boundary elements of the two schedules — the flat
    union all_gather moves O(p x C_loc) padded rows to every worker,
    the tree moves O(capacity) rows per round over ⌈log₂(W)⌉ rounds plus
    one capacity-sized broadcast — and picks the smaller. Without a
    workers axis (``axis_size`` None or 1) the union is device-local and
    'auto' resolves to 'flat'; the engine overrides 'auto' with its
    calibrated per-bucket choice (`calibrate_shard_threshold`)."""
    if cfg.merge not in ("flat", "tree", "auto"):
        raise ValueError(f"unknown merge mode {cfg.merge!r} "
                         f"(expected flat | tree | auto)")
    if cfg.merge != "auto":
        return cfg.merge
    if not axis_size or axis_size < 2 or p_total is None:
        return "flat"
    cap = min(p_total * local_cap, max(cfg.capacity, 1))
    flat_elems = p_total * local_cap * d
    tree_elems = (merge_rounds(axis_size) + 2) * cap * (d + 1)
    return "tree" if flat_elems > tree_elems else "flat"


# wire packing: ONE tensor per ppermute round — points, the validity
# mask as a 1.0/0.0 column, and (NoSeq) per-row partition ids / grid
# cells as exact small-integer float columns (ids stay far below the
# 2^24 f32 mantissa bound)
_WIRE_UINT = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _pack_wire(pts, msk, parts=None, cells=None):
    cols = [pts, msk.astype(pts.dtype)[:, None]]
    if parts is not None:
        cols.append(parts.astype(pts.dtype)[:, None])
        cols.append(cells.astype(pts.dtype))
    return jnp.concatenate(cols, axis=1)


def _root_broadcast(wire, axis_name):
    """Replicate worker 0's buffer to the whole axis, bit-exactly.

    A float psum of where(root, x, 0) would corrupt negative zeros
    (-0.0 + 0.0 == +0.0), so the buffer is bitcast to unsigned ints —
    only the root contributes a nonzero term, making the integer sum an
    exact copy of the root's bits."""
    bits = jax.lax.bitcast_convert_type(
        wire, _WIRE_UINT[jnp.dtype(wire.dtype).itemsize])
    root = jnp.equal(jax.lax.axis_index(axis_name), 0)
    bits = jnp.where(root, bits, jnp.zeros_like(bits))
    return jax.lax.bitcast_convert_type(jax.lax.psum(bits, axis_name),
                                        wire.dtype)


def _tree_merge(sky: SkyBuffer, cfg: SkyConfig, *, part_idx_local,
                cells_local, axis_name: str, axis_size: int):
    """Hierarchical merge: ⌈log₂(W)⌉ pruning ppermute rounds.

    Round r (stride s = 2^r) sends worker i+s's compacted buffer to
    worker i for every receiver i ≡ 0 (mod 2s) — a reduce-to-root
    schedule that is exact for any worker count: a sender holds exactly
    r factors of two in its index, so it never participates again and
    its (already forwarded) buffer is never re-read. Workers outside the
    round's partial permutation receive zeros (an all-masked buffer) and
    re-sweep their own antichain, keeping the program SPMD-uniform
    without touching the result. After the rounds worker 0 holds the
    pruned union; one bit-exact psum broadcast replicates it.

    Every boundary tensor is O(capacity) rows — never the p x C_loc
    padded union the flat all_gather ships. Survivor sets match the flat
    merge exactly (dominance is transitive, so a dominator pruned
    in-round is itself dominated by a surviving row of the same buffer;
    NoSeq's potential-dominator relation is closed under that chain —
    see `noseq.relative_rows_mask`), and the shared canonical total
    order makes the output bit-for-bit equal whenever no overflow
    occurred. Overflow reduces to "union > min(p x C_loc, capacity)" in
    both modes, so the flag matches even when truncation differs."""
    p_local, local_cap, d = sky.points.shape
    w = int(axis_size)
    union_size = jax.lax.psum(jnp.sum(sky.mask), axis_name)
    flat = sky.points.reshape(-1, d)
    fmask = sky.mask.reshape(-1)
    cap_u = min(w * flat.shape[0], max(cfg.capacity, 1))
    overflow = union_size > cap_u

    if not cfg.noseq:
        # worker-local reduce: the flat merge's math restricted to this
        # worker's shard (at W=1 this IS the flat merge, bit for bit)
        own = compact(flat, fmask,
                      min(flat.shape[0], max(cfg.capacity, 1)))
        buf = block_sfs(own.points, own.mask, capacity=cfg.capacity,
                        block=cfg.block, impl=cfg.impl, wtile=cfg.wtile)
        sweep_blocks = jax.lax.psum(-(-jnp.sum(own.mask) // cfg.block),
                                    axis_name)
        pts, msk = buf.points, buf.mask

        dom_impl = resolve_spec(cfg.impl).dominance
        rows = pts.shape[0]
        for r in range(merge_rounds(w)):
            s = 1 << r
            perm = [(i + s, i) for i in range(0, w - s, 2 * s)]
            rcv = jax.lax.ppermute(_pack_wire(pts, msk), axis_name, perm)
            rpts, rmsk = rcv[:, :d], rcv[:, d] > 0.5
            # both sides are already antichains, so a pairwise dominance
            # cross-filter yields exactly the union's skyline without
            # re-running the sequential sweep: if a row were dropped by
            # a cross-side dominator that itself dies in-round, its
            # killer (same side as the dominator, by transitivity) would
            # contradict that side being dominance-free
            keep_own = filtering.filter_by_representatives(
                pts, msk, rpts, rmsk, impl=dom_impl)
            keep_rcv = filtering.filter_by_representatives(
                rpts, rmsk, pts, msk, impl=dom_impl)
            # survivors fit `rows` whenever the union did not overflow
            # (> capacity survivors implies union_size > cap_u, already
            # flagged above); under overflow truncation may differ from
            # the flat schedule, like every other overflow regime
            out = compact(jnp.concatenate([pts, rpts]),
                          jnp.concatenate([keep_own, keep_rcv]), rows)
            pts, msk = out.points, out.mask

        wire = _root_broadcast(_pack_wire(pts, msk), axis_name)
        pts, msk = wire[:, :d], wire[:, d] > 0.5
        pts = apply_sentinel(pts, msk)
        order = canonical_order(pts, msk)
        final = SkyBuffer(pts[order], msk[order],
                          jnp.sum(msk).astype(jnp.int32), overflow)
        return final, {"union_size": union_size,
                       "merge_sweep_blocks": sweep_blocks}

    # NoSeq: rows keep their origin partition (and grid cell) so the
    # potential-dominator mask is evaluated per row pair in-round
    parts = jnp.repeat(part_idx_local, local_cap)
    cells = jnp.repeat(cells_local, local_cap, axis=0)
    take = min(flat.shape[0], cap_u)
    order = compact_order(fmask, take)
    pts, msk = flat[order], fmask[order]
    pparts, pcells = parts[order], cells[order]
    if take < cap_u:
        # pad to the global survivor budget so in-round survivors never
        # truncate before the union itself overflows
        pts = jnp.pad(pts, ((0, cap_u - take), (0, 0)))
        msk = jnp.pad(msk, (0, cap_u - take))
        pparts = jnp.pad(pparts, (0, cap_u - take))
        pcells = jnp.pad(pcells, ((0, cap_u - take), (0, 0)))
    # the flat merge's count of potential-dominator rows, from the
    # partitions' ids, cells and sliced maxima and this worker's rows
    all_gather = lambda x: jax.lax.all_gather(x, axis_name, axis=0,
                                              tiled=True)
    pd_refs = jax.lax.psum(noseq.pd_refs(
        cfg.strategy, all_gather(part_idx_local), all_gather(cells_local),
        all_gather(noseq.slice_max(sky.points, sky.mask, cfg.sliced_dim)),
        parts, cells, flat[:, cfg.sliced_dim], fmask), axis_name)
    # self-filter within the worker (covers the same-shard pairs the
    # flat merge tests through the full gathered reference set)
    msk = noseq.relative_rows_mask(pts, msk, pparts, pcells,
                                   strategy=cfg.strategy, block=cfg.block,
                                   dim=cfg.sliced_dim)

    for r in range(merge_rounds(w)):
        s = 1 << r
        perm = [(i + s, i) for i in range(0, w - s, 2 * s)]
        rcv = jax.lax.ppermute(_pack_wire(pts, msk, pparts, pcells),
                               axis_name, perm)
        cpts = jnp.concatenate([pts, rcv[:, :d]])
        cmsk = jnp.concatenate([msk, rcv[:, d] > 0.5])
        cparts = jnp.concatenate(
            [pparts, rcv[:, d + 1].astype(jnp.int32)])
        ccells = jnp.concatenate(
            [pcells, rcv[:, d + 2:].astype(jnp.int32)])
        cmsk = noseq.relative_rows_mask(cpts, cmsk, cparts, ccells,
                                        strategy=cfg.strategy,
                                        block=cfg.block,
                                        dim=cfg.sliced_dim)
        order = compact_order(cmsk, cap_u)
        pts, msk = cpts[order], cmsk[order]
        pparts, pcells = cparts[order], ccells[order]

    wire = _root_broadcast(_pack_wire(pts, msk, pparts, pcells), axis_name)
    pts, msk = wire[:, :d], wire[:, d] > 0.5
    order = canonical_order(pts, msk)
    final = compact(pts[order], msk[order], cfg.capacity)
    final = SkyBuffer(final.points, final.mask, final.count,
                      final.overflow | overflow)
    return final, {"union_size": union_size, "noseq_pd_refs": pd_refs}


def merge_stage(sky: SkyBuffer, meta, cfg: SkyConfig, *,
                part_idx_local=None, cells_local=None, gather=None,
                axis_name=None, axis_size=None):
    if gather is None:
        gather = lambda x: x
    p_local, local_cap, d = sky.points.shape
    if part_idx_local is None:
        part_idx_local = meta["part_idx"]
    if cells_local is None:
        cells_local = meta["cells"]

    mode = resolve_merge(cfg, axis_size=axis_size,
                         p_total=p_local * (axis_size or 1),
                         local_cap=local_cap, d=d)
    # tree mode needs a workers axis to permute over; mesh-free contexts
    # (single device, the windowed merge-on-read, the engine vmap path)
    # run the identical flat math — the merge mode only changes the
    # collective schedule, never the result bits
    if mode == "tree" and axis_name is not None:
        return _tree_merge(sky, cfg, part_idx_local=part_idx_local,
                           cells_local=cells_local, axis_name=axis_name,
                           axis_size=axis_size)

    u_pts = gather(sky.points)        # (p, C_loc, d)
    u_mask = gather(sky.mask)
    u_parts = gather(part_idx_local)  # (p,)
    union_size = jnp.sum(u_mask)

    if not cfg.noseq:
        flat = u_pts.reshape(-1, d)
        fmask = u_mask.reshape(-1)
        # compact the union first: the final pass must scan |u| tuples,
        # not p x capacity padded rows (models "only the local skylines
        # are communicated", paper Alg. 2 line 4)
        cap_u = min(flat.shape[0], max(cfg.capacity, 1))
        u_compact = compact(flat, fmask, cap_u)
        # the final sequential pass reuses the same one-call fused-sweep
        # entry as the local phase (block_sfs is its single-partition
        # wrapper)
        final = block_sfs(u_compact.points, u_compact.mask,
                          capacity=cfg.capacity, block=cfg.block,
                          impl=cfg.impl, wtile=cfg.wtile)
        # canonicalize: block-SFS emits members in score order but breaks
        # score ties by its input (partition-gather) order; the total
        # lexicographic tie-break makes the merge output independent of
        # how the data reached it, which the incremental path relies on
        # for bitwise chunking-invariance
        order = canonical_order(final.points, final.mask)
        overflow = final.overflow | u_compact.overflow
        final = SkyBuffer(final.points[order], final.mask[order],
                          final.count, overflow)
        return final, {"union_size": union_size,
                       "merge_sweep_blocks":
                           -(-jnp.sum(u_compact.mask) // cfg.block)}

    refs = u_pts.reshape(-1, d)
    refmask = u_mask.reshape(-1)
    ref_parts = jnp.repeat(u_parts, local_cap)
    ref_cells = jnp.repeat(gather(cells_local), local_cap, axis=0)
    # compact the gathered union (valid rows first, truncated) through
    # the same shared `compact` helper as the sequential branch, so each
    # worker tests against |u| refs, not p x capacity padded rows — and
    # the union-truncation overflow accounting is identical in both
    # branches
    cap_u = min(refs.shape[0], max(cfg.capacity, 1))
    u_compact = compact(refs, refmask, cap_u)
    order = compact_order(refmask, cap_u)
    refs, refmask = u_compact.points, u_compact.mask
    ref_parts = ref_parts[order]
    ref_cells = ref_cells[order]

    ref_vals = refs[:, cfg.sliced_dim]
    dom_impl = resolve_spec(cfg.impl).dominance

    def filter_one(u_i, m_i, own_part, own_cell, own_max):
        pd = noseq.pd_row_mask(cfg.strategy, own_part, ref_parts,
                               own_cell, ref_cells, own_max, ref_vals)
        keep = noseq.relative_skyline_mask(u_i, m_i, refs, refmask, pd,
                                           impl=dom_impl)
        return keep, jnp.sum(refmask & pd, dtype=jnp.int32)

    final_mask_local, pd_refs = jax.vmap(filter_one)(
        sky.points, sky.mask, part_idx_local, cells_local,
        noseq.slice_max(sky.points, sky.mask, cfg.sliced_dim))
    # assemble a single replicated result buffer, in canonical order
    # (total: score, then lexicographic coordinates) before compaction,
    # so the merge output is independent of the partition layout — the
    # same order the sequential merge emits, which the incremental path
    # (repro.core.incremental) relies on for bitwise chunking-invariance
    all_pts = gather(sky.points).reshape(-1, d)
    all_mask = gather(final_mask_local).reshape(-1)
    order = canonical_order(all_pts, all_mask)
    final = compact(all_pts[order], all_mask[order], cfg.capacity)
    final = SkyBuffer(final.points, final.mask, final.count,
                      final.overflow | u_compact.overflow)
    # valid rows each partition was tested against, summed: the useful
    # part of the p x C_loc x |u| padded test
    return final, {"union_size": union_size,
                   "noseq_pd_refs": jnp.sum(gather(pd_refs))}


# --------------------------------------------------------------------------
# Public entry point: one jitted program for partition + local + merge
# --------------------------------------------------------------------------

# Python-side effect executed once per trace of the fused pipeline — a
# traced-callback counter. jit's cache makes repeated same-shape calls
# skip tracing entirely, so tests can assert "compiled once" by reading
# trace_count() around a loop of calls.
_TRACE_EVENTS: collections.Counter[str] = collections.Counter()


def trace_count(label: str = "fused") -> int:
    """How many times the fused pipeline has been (re)traced."""
    return _TRACE_EVENTS[label]


def _local_merge(bufs, bmask, key, part_idx, cells, *, cfg: SkyConfig,
                 meta, gather, axis_name=None, axis_size=None):
    """One query's phase 1 + phase 2 on this worker's partitions.

    Shared by every execution mode: single-device (gather = identity),
    1-D workers shard_map, and the 2-D queries x workers program (where
    this body runs under an outer vmap over the local query shard).
    ``axis_name``/``axis_size`` name the workers mesh axis when running
    under shard_map — the tree merge permutes over it; without an axis
    the merge runs the flat schedule (same bits)."""
    sky, s2 = local_stage(bufs, bmask, cfg, key=key, gather=gather)
    with jax.named_scope(MERGE_SCOPE):
        final, s3 = merge_stage(sky, meta, cfg, part_idx_local=part_idx,
                                cells_local=cells, gather=gather,
                                axis_name=axis_name, axis_size=axis_size)
    return final, dict(s2, **s3)


def _body_stat_keys(cfg: SkyConfig) -> tuple[str, ...]:
    """Stats emitted by `_local_merge` (shard_map out_specs need them)."""
    return ("local_sizes", "local_overflow", "union_size", "sweep_blocks",
            *(("rep_filter_dropped",) if cfg.rep_filter else ()),
            *(("noseq_pd_refs",) if cfg.noseq else ("merge_sweep_blocks",)))


def _fused(pts, mask, key, *, cfg: SkyConfig, mesh, axis_name: str):
    """The whole pipeline as one traceable function (no host sync).

    A thin wrapper over `repro.core.incremental`: one-shot execution is
    "insert everything into an empty SkylineState" — the fresh-state
    insert statically skips the pre-filter/evict passes, so the body is
    exactly the partition+local+merge program, and the returned buffer is
    the state's packed antichain (already in canonical SFS score order).
    """
    from repro.core import incremental
    _TRACE_EVENTS["fused"] += 1
    state, stats = incremental._insert(None, pts, mask, key, cfg=cfg,
                                       mesh=mesh, axis_name=axis_name)
    return (SkyBuffer(state.points, state.mask, state.count,
                      state.overflow), stats)


def _fused_batch(pts, mask, keys, *, cfg: SkyConfig, mesh,
                 q_axis: str, w_axis: str):
    """A (Q, N, d) query batch as one 2-D (queries x workers) program.

    The query batch is sharded over `q_axis` while each query's routed
    partition buckets are sharded over `w_axis`; within a query shard the
    local+merge body is vmapped over the queries it holds, and
    collectives (all_gather of representatives / local skylines) run over
    `w_axis` only — each query merges against its own partitions. This is
    the engine's large-N regime: vmap-over-queries alone leaves the
    workers mesh idle, tuple-sharding alone leaves query parallelism on
    the table; the 2-D mesh buys both at once.

    Like `_fused`, a thin wrapper over the batched fresh-state insert of
    `repro.core.incremental` (Q empty states fed in one dispatch).
    """
    from repro.core import incremental
    _TRACE_EVENTS["fused_batch"] += 1
    state, stats = incremental._insert_batch(None, pts, mask, keys,
                                             cfg=cfg, mesh=mesh,
                                             q_axis=q_axis, w_axis=w_axis)
    return (SkyBuffer(state.points, state.mask, state.count,
                      state.overflow), stats)


@functools.lru_cache(maxsize=None)
def fused_skyline_fn(cfg: SkyConfig, mesh: jax.sharding.Mesh | None = None,
                     axis_name: str = "workers"):
    """The jitted fused pipeline for a given config/mesh.

    Signature of the returned callable: ``(pts, mask, key) -> (SkyBuffer,
    stats)`` with mask/key required (pass ``jnp.ones(n, bool)`` /
    ``jax.random.PRNGKey(0)`` for the defaults). Cached so every caller
    with the same (cfg, mesh, axis_name) shares one jit cache — repeated
    same-shape queries compile exactly once.
    """
    return jax.jit(functools.partial(_fused, cfg=cfg, mesh=mesh,
                                     axis_name=axis_name))


@functools.lru_cache(maxsize=None)
def fused_skyline_batch_fn(cfg: SkyConfig,
                           mesh: jax.sharding.Mesh | None = None,
                           q_axis: str = "queries",
                           w_axis: str = "workers"):
    """The jitted batched pipeline: ``(pts (Q, N, d), mask (Q, N),
    keys (Q, ...)) -> (SkyBuffer, stats)`` with a leading Q axis on every
    output leaf.

    Without a mesh this is plain vmap-over-queries of the fused program
    (the engine's small-query path). With a 2-D mesh carrying `q_axis`
    and `w_axis` it is the queries x workers sharded program: Q must be a
    multiple of the `q_axis` size and cfg's partition count a multiple of
    the `w_axis` size. Both variants are bit-for-bit equivalent — the
    sharded program runs the identical comparison/selection math, only
    placed across devices.
    """
    if mesh is None:
        return jax.jit(jax.vmap(functools.partial(
            _fused, cfg=cfg, mesh=None, axis_name=w_axis)))
    return jax.jit(functools.partial(_fused_batch, cfg=cfg, mesh=mesh,
                                     q_axis=q_axis, w_axis=w_axis))


def parallel_skyline(pts: jnp.ndarray, mask: jnp.ndarray | None = None, *,
                     cfg: SkyConfig = SkyConfig(),
                     key: jax.Array | None = None,
                     mesh: jax.sharding.Mesh | None = None,
                     axis_name: str = "workers"):
    """Compute SKY(pts) with the parallel pattern of the paper.

    Returns (SkyBuffer, stats). With `mesh`, partitions are sharded over
    `axis_name` and executed under shard_map; p must be a multiple of the
    mesh axis size. partition -> local -> merge execute as a single
    device-resident program: no intermediate device_put, and the stats
    pytree is made of device arrays (host sync only when read). The host
    work of the call (defaults, program lookup, enqueue) runs under the
    profiler span `DISPATCH_SPAN`.
    """
    with jax.profiler.TraceAnnotation(DISPATCH_SPAN):
        n = pts.shape[0]
        if mask is None:
            mask = jnp.ones((n,), jnp.bool_)
        if key is None:
            key = jax.random.PRNGKey(0)
        return fused_skyline_fn(cfg, mesh, axis_name)(pts, mask, key)
