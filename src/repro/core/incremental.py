"""Device-resident incremental skyline maintenance (`SkylineState`).

The paper's block-structured sequential filtering — local skylines merged
against a retained candidate buffer — is naturally incremental: the
retained buffer IS a running skyline, and an arriving chunk only has to be
(a) filtered against it, (b) reduced to its own skyline, and (c) merged
back, evicting members the new tuples dominate. This module makes that
buffer a first-class, device-resident pytree and the single currency of
every execution path:

  ``SkylineState``  — packed skyline buffer + validity mask + running
                      stats (count / overflow / tuples seen / chunks fed),
                      optionally carrying a leading Q axis so Q live
                      skylines are maintained in ONE dispatch.
  ``init_state``    — empty state (all-masked buffer, zeroed stats).
  ``insert_chunk``  — filter an arriving chunk against the live skyline,
                      compute the survivors' skyline with the fused
                      partition+local+merge pipeline, evict newly
                      dominated members, and merge — one compaction pass,
                      one jitted program, no host round-trip.
  ``finalize``      — canonicalize the state into a ``SkyBuffer``
                      (SFS score order, compacted) bit-for-bit equal to
                      the one-shot ``parallel_skyline`` answer for the
                      same data, regardless of how it was chunked.

The one-shot entry points (`repro.core.parallel.fused_skyline_fn` /
`fused_skyline_batch_fn`) are thin wrappers over this module: "init from
an empty state + feed everything" — statically specialised so the empty
pre-filter/evict passes fold away to exactly the old pipeline.

Exactness of the incremental step (all by dominance transitivity):

  * pre-filter: a chunk tuple dominated by a live member can only lose
    its dominator to a *new* tuple that dominates the dominator — and
    hence the chunk tuple too; dropping it early is safe.
  * eviction: any chunk tuple dominating a live member is either itself a
    surviving new member or is dominated by one (never by a live member —
    the live buffer is an antichain), so testing the live buffer against
    the chunk *survivors* alone is complete.

Together these keep the invariant: after every insert, the state holds
exactly SKY(all valid tuples fed so far).

Batched inserts shard over the engine's 2-D ``(queries, workers)`` mesh:
the Q states and chunks over ``queries``, each chunk's partition buckets
over ``workers`` — same placement as the one-shot batch program.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import parallel as par
from repro.core.dominance import (SENTINEL, apply_sentinel, canonical_order,
                                  dominated_mask)
from repro.core.parallel import SkyConfig
from repro.core.sfs import SkyBuffer, compact
from repro.kernels.backend import resolve_spec

__all__ = ["SkylineState", "state_capacity", "init_state", "insert_chunk",
           "finalize", "insert_chunk_fn", "insert_chunk_batch_fn",
           "finalize_fn"]


class SkylineState(NamedTuple):
    """Fixed-capacity running skyline, resident on device between chunks.

    Leaves are either unbatched (one live skyline) or carry a leading Q
    axis (Q live skylines maintained together). The buffer is always an
    antichain holding exactly the skyline of every valid tuple fed so far
    (whenever no capacity overflow occurred — ``overflow`` reports it).
    """
    points: jnp.ndarray    # (C, d) or (Q, C, d) packed members
    mask: jnp.ndarray      # (C,) or (Q, C) bool validity
    count: jnp.ndarray     # () or (Q,) int32 — live skyline size
    overflow: jnp.ndarray  # () or (Q,) bool — capacity ever exceeded
    seen: jnp.ndarray      # () or (Q,) int32 — valid tuples fed so far
    chunks: jnp.ndarray    # () or (Q,) int32 — insert_chunk calls absorbed


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def state_capacity(cfg: SkyConfig) -> int:
    """Row count of the state buffer: the final-merge window size of the
    fused pipeline (capacity rounded up to the dominance block), so the
    one-shot answer drops into a state with no reshaping."""
    return _ceil_to(max(cfg.capacity, 1), cfg.block)


def init_state(cfg: SkyConfig, d: int, *, dtype=jnp.float32,
               q: int | None = None) -> SkylineState:
    """Empty state for ``d``-attribute tuples; ``q`` adds a leading batch
    axis (q live skylines). All leaves are device arrays from the start —
    the state never lives on the host."""
    lead = () if q is None else (q,)
    c = state_capacity(cfg)
    return SkylineState(
        points=jnp.full(lead + (c, d), SENTINEL, dtype),
        mask=jnp.zeros(lead + (c,), jnp.bool_),
        count=jnp.zeros(lead, jnp.int32),
        overflow=jnp.zeros(lead, jnp.bool_),
        seen=jnp.zeros(lead, jnp.int32),
        chunks=jnp.zeros(lead, jnp.int32))


def _fit_rows(points: jnp.ndarray, mask: jnp.ndarray, rows: int):
    """Pad (sentinel/False) or truncate the row axis to ``rows``.

    The merge window of the fused pipeline is capacity rounded to the
    *effective* block (block is clipped to the union size for tiny
    unions), so its row count can differ from ``state_capacity``;
    truncation is safe because members never exceed the compacted union
    size, which is below the state capacity whenever shapes diverge."""
    c = points.shape[-2]
    if c == rows:
        return points, mask
    if c > rows:
        return points[..., :rows, :], mask[..., :rows]
    pw_p = [(0, 0)] * points.ndim
    pw_p[-2] = (0, rows - c)
    pw_m = [(0, 0)] * mask.ndim
    pw_m[-1] = (0, rows - c)
    return (jnp.pad(points, pw_p, constant_values=SENTINEL),
            jnp.pad(mask, pw_m, constant_values=False))


# --------------------------------------------------------------------------
# The chunk pipeline: one query's partition+local+merge (the former
# parallel._fused / _fused_batch bodies, now the skyline reduction every
# insert — and every one-shot call — runs on its input)
# --------------------------------------------------------------------------

def _chunk_skyline(pts, mask, key, *, cfg: SkyConfig, mesh, axis_name: str):
    """SKY(chunk) via partition -> local -> merge, optionally shard_mapped
    over a 1-D ``workers`` mesh (no host sync; see repro.core.parallel)."""
    # the partition scope spans routing and the hand-off to the workers
    # (the local key's derivation included), in the order they trace
    with jax.named_scope(par.PARTITION_SCOPE):
        buckets, meta, stats = par.partition_stage(pts, mask, cfg, key)
        p = meta["p"]
        if mesh is None:
            local_key = jax.random.fold_in(key, 1)
        else:
            nworkers = mesh.shape[axis_name]
            if p % nworkers != 0:
                raise ValueError(
                    f"p={p} not divisible by {nworkers} workers")
            # Hand the routed buckets to the workers axis *inside* the
            # same program — a sharding constraint, not a host transfer.
            spec = NamedSharding(mesh, P(axis_name))
            bufs = jax.lax.with_sharding_constraint(buckets.points, spec)
            bmask = jax.lax.with_sharding_constraint(buckets.mask, spec)
            part_idx = jax.lax.with_sharding_constraint(meta["part_idx"],
                                                        spec)
            cells = jax.lax.with_sharding_constraint(meta["cells"], spec)
            local_key = jax.random.fold_in(key, 1)

    if mesh is None:
        final, s2 = par._local_merge(
            buckets.points, buckets.mask, local_key,
            meta["part_idx"], meta["cells"], cfg=cfg, meta=meta,
            gather=lambda x: x)
    else:

        def body(bufs, bmask, part_idx, cells, local_key):
            gather = lambda x: jax.lax.all_gather(
                x, axis_name, axis=0, tiled=True)
            final, s2 = par._local_merge(bufs, bmask, local_key, part_idx,
                                         cells, cfg=cfg, meta=meta,
                                         gather=gather, axis_name=axis_name,
                                         axis_size=nworkers)
            # gather per-partition stats, keep scalars replicated
            with jax.named_scope(par.MERGE_SCOPE):
                s2["local_sizes"] = gather(s2["local_sizes"])
                s2["sweep_blocks"] = jax.lax.psum(s2["sweep_blocks"],
                                                  axis_name)
            return final, s2

        final, s2 = shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(axis_name),
                      P(axis_name), P()),
            out_specs=(SkyBuffer(P(), P(), P(), P()),
                       {k: P() for k in par._body_stat_keys(cfg)}),
            check_vma=False)(bufs, bmask, part_idx, cells, local_key)

    stats.update(s2)
    with jax.named_scope(par.MERGE_SCOPE):
        overflow = (buckets.overflow | stats.get("local_overflow", False)
                    | final.overflow)
    final = SkyBuffer(final.points, final.mask, final.count, overflow)
    return final, stats


def _chunk_skyline_batch(pts, mask, keys, *, cfg: SkyConfig, mesh,
                         q_axis: str, w_axis: str):
    """A (Q, N, d) chunk batch as one 2-D (queries x workers) program.

    The query batch is sharded over `q_axis` while each query's routed
    partition buckets are sharded over `w_axis`; within a query shard the
    local+merge body is vmapped over the queries it holds, and
    collectives (all_gather of representatives / local skylines) run over
    `w_axis` only — each query merges against its own partitions.
    """
    qb, _, d = pts.shape
    p, m = par.effective_parts(cfg, d)
    nq, nw = mesh.shape[q_axis], mesh.shape[w_axis]
    if p % nw != 0:
        raise ValueError(f"p={p} not divisible by {nw} workers")
    if qb % nq != 0:
        raise ValueError(f"Q={qb} not divisible by {nq} query shards")

    def part_one(pts_i, mask_i, key_i):
        buckets, _, stats = par.partition_stage(pts_i, mask_i, cfg, key_i)
        return buckets, stats

    with jax.named_scope(par.PARTITION_SCOPE):
        buckets, stats = jax.vmap(part_one)(pts, mask, keys)
        # per-partition metadata is query-independent — build it once,
        # and shard it over the workers axis only (no queries dimension)
        cells = (par._grid_cells(p, m, d) if cfg.strategy == "grid"
                 else jnp.zeros((p, d), jnp.int32))
        part_idx = jnp.arange(p, dtype=jnp.int32)
        meta = {"p": p, "m": m, "cells": cells, "part_idx": part_idx}

        spec_qw = NamedSharding(mesh, P(q_axis, w_axis))
        spec_w = NamedSharding(mesh, P(w_axis))
        bufs = jax.lax.with_sharding_constraint(buckets.points, spec_qw)
        bmask = jax.lax.with_sharding_constraint(buckets.mask, spec_qw)
        part_idx = jax.lax.with_sharding_constraint(part_idx, spec_w)
        cells = jax.lax.with_sharding_constraint(cells, spec_w)
        local_keys = jax.lax.with_sharding_constraint(
            jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys),
            NamedSharding(mesh, P(q_axis)))

    def body(bufs, bmask, part_idx, cells, local_keys):
        gather = lambda x: jax.lax.all_gather(x, w_axis, axis=0, tiled=True)

        def one(b, bm, k):
            final, s2 = par._local_merge(b, bm, k, part_idx, cells, cfg=cfg,
                                         meta=meta, gather=gather,
                                         axis_name=w_axis, axis_size=nw)
            with jax.named_scope(par.MERGE_SCOPE):
                s2["local_sizes"] = gather(s2["local_sizes"])
                s2["sweep_blocks"] = jax.lax.psum(s2["sweep_blocks"],
                                                  w_axis)
            return final, s2

        return jax.vmap(one)(bufs, bmask, local_keys)

    final, s2 = shard_map(
        body, mesh=mesh,
        in_specs=(P(q_axis, w_axis), P(q_axis, w_axis), P(w_axis),
                  P(w_axis), P(q_axis)),
        out_specs=(SkyBuffer(P(q_axis), P(q_axis), P(q_axis), P(q_axis)),
                   {k: P(q_axis) for k in par._body_stat_keys(cfg)}),
        check_vma=False)(bufs, bmask, part_idx, cells, local_keys)

    stats.update(s2)
    with jax.named_scope(par.MERGE_SCOPE):
        overflow = (buckets.overflow | s2["local_overflow"]
                    | final.overflow)
    final = SkyBuffer(final.points, final.mask, final.count, overflow)
    return final, stats


# --------------------------------------------------------------------------
# Insert: pre-filter -> chunk skyline -> evict -> one-pass compact merge
# --------------------------------------------------------------------------

def _insert(state: SkylineState | None, pts, mask, key, *, cfg: SkyConfig,
            mesh, axis_name: str):
    """One query's insert step (traceable). ``state=None`` is the
    statically-fresh path: pre-filter and eviction fold away and the body
    is exactly the one-shot fused pipeline — this is what makes
    `fused_skyline_fn` a zero-overhead wrapper.

    The row count is the *state's* (== `state_capacity` for ordinary
    states; windowed epoch sub-states may carry fewer rows — their
    retained-candidate buffers are sized to epoch fronts, not the whole
    window). A skyline outgrowing the rows sets the overflow flag."""
    c = state_capacity(cfg) if state is None else state.points.shape[-2]
    # pre-filter/evict are pairwise passes between two different point
    # sets (chunk vs live antichain): they use the backend spec's
    # dominance kernel, while the reduction inside `_chunk_skyline` goes
    # through the fused sweep
    dom_impl = resolve_spec(cfg.impl).dominance
    stats: dict[str, Any] = {}
    if state is not None:
        stats["chunk_arrivals"] = jnp.sum(mask).astype(jnp.int32)
        # pre-filter the arriving chunk against the live skyline
        mask = mask & ~dominated_mask(pts, state.points, state.mask,
                                      impl=dom_impl)
    sky, pstats = _chunk_skyline(pts, mask, key, cfg=cfg, mesh=mesh,
                                 axis_name=axis_name)
    stats.update(pstats)
    new_pts, new_mask = _fit_rows(sky.points, sky.mask, c)

    if state is None:
        nst = SkylineState(new_pts, new_mask, sky.count, sky.overflow,
                           seen=stats["n_valid"].astype(jnp.int32),
                           chunks=jnp.int32(1))
        return nst, stats

    # evict live members newly dominated by the chunk's survivors, then
    # merge both antichains with one stable compaction pass
    evict = state.mask & dominated_mask(state.points, new_pts, new_mask,
                                        impl=dom_impl)
    merged = compact(jnp.concatenate([state.points, new_pts]),
                     jnp.concatenate([state.mask & ~evict, new_mask]), c)
    overflow = (state.overflow | sky.overflow | merged.overflow
                | (merged.count > cfg.capacity) | (sky.count > c))
    nst = SkylineState(merged.points, merged.mask, merged.count, overflow,
                       seen=state.seen + stats["chunk_arrivals"],
                       chunks=state.chunks + 1)
    stats["evicted"] = jnp.sum(evict).astype(jnp.int32)
    stats["inserted"] = sky.count
    return nst, stats


def _insert_batch(state: SkylineState | None, pts, mask, keys, *,
                  cfg: SkyConfig, mesh, q_axis: str, w_axis: str):
    """Q live skylines advanced in one dispatch. Without a mesh the body
    is vmap-over-queries of `_insert`; with a 2-D mesh the states and
    chunks shard over ``q_axis`` and each chunk's partitions over
    ``w_axis`` (same placement as the one-shot batch program)."""
    if mesh is None:
        one = functools.partial(_insert, cfg=cfg, mesh=None,
                                axis_name=w_axis)
        if state is None:
            return jax.vmap(lambda p, m, k: one(None, p, m, k))(
                pts, mask, keys)
        return jax.vmap(one)(state, pts, mask, keys)

    c = state_capacity(cfg) if state is None else state.points.shape[-2]
    dom_impl = resolve_spec(cfg.impl).dominance
    spec_q = NamedSharding(mesh, P(q_axis))
    stats: dict[str, Any] = {}
    if state is not None:
        sp = jax.lax.with_sharding_constraint(state.points, spec_q)
        sm = jax.lax.with_sharding_constraint(state.mask, spec_q)
        stats["chunk_arrivals"] = jnp.sum(mask, axis=1).astype(jnp.int32)
        mask = mask & ~jax.vmap(
            lambda x, rp, rm: dominated_mask(x, rp, rm, impl=dom_impl))(
            pts, sp, sm)

    sky, pstats = _chunk_skyline_batch(pts, mask, keys, cfg=cfg, mesh=mesh,
                                       q_axis=q_axis, w_axis=w_axis)
    stats.update(pstats)
    new_pts, new_mask = _fit_rows(sky.points, sky.mask, c)
    new_pts = jax.lax.with_sharding_constraint(new_pts, spec_q)

    if state is None:
        nst = SkylineState(new_pts, new_mask, sky.count, sky.overflow,
                           seen=stats["n_valid"].astype(jnp.int32),
                           chunks=jnp.ones_like(sky.count))
        return nst, stats

    evict = state.mask & jax.vmap(
        lambda x, rp, rm: dominated_mask(x, rp, rm, impl=dom_impl))(
        sp, new_pts, new_mask)
    merged = jax.vmap(lambda p, m: compact(p, m, c))(
        jnp.concatenate([sp, new_pts], axis=1),
        jnp.concatenate([state.mask & ~evict, new_mask], axis=1))
    overflow = (state.overflow | sky.overflow | merged.overflow
                | (merged.count > cfg.capacity) | (sky.count > c))
    nst = SkylineState(merged.points, merged.mask, merged.count, overflow,
                       seen=state.seen + stats["chunk_arrivals"],
                       chunks=state.chunks + 1)
    stats["evicted"] = jnp.sum(evict, axis=1).astype(jnp.int32)
    stats["inserted"] = sky.count
    return nst, stats


def _finalize(state: SkylineState, *, cfg: SkyConfig) -> SkyBuffer:
    """Canonicalize the state: total-order sort (monotone score, then
    lexicographic coordinates — `canonical_order`) + sentinel fill. The
    state is an antichain by invariant, so no dominance tests are needed
    — and because the order is a *total* order on point values, the
    result is bit-for-bit the one-shot fused pipeline's merge output for
    the same data (both merge modes canonicalize the same way),
    regardless of arrival order or score ties."""
    order = canonical_order(state.points, state.mask)
    mask = state.mask[order]
    return SkyBuffer(apply_sentinel(state.points[order], mask), mask,
                     state.count, state.overflow)


# --------------------------------------------------------------------------
# Jitted entry points, cached per (cfg, mesh, axis names) like the fused
# pipeline — repeated same-shape chunks never retrace
# (`parallel.trace_count("insert"/"insert_batch")` observes).
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def insert_chunk_fn(cfg: SkyConfig, mesh: jax.sharding.Mesh | None = None,
                    axis_name: str = "workers"):
    """Jitted ``(state, pts, mask, key) -> (state', stats)`` for one live
    skyline. Mask/key are required (pass ``jnp.ones(n, bool)`` /
    ``jax.random.PRNGKey(0)`` for the defaults)."""

    def run(state, pts, mask, key):
        par._TRACE_EVENTS["insert"] += 1
        return _insert(state, pts, mask, key, cfg=cfg, mesh=mesh,
                       axis_name=axis_name)

    # single-owner update: the incoming state's buffers are reused for
    # state' (callers rebind `state, _ = ins(state, ...)`); cfg.donate=False
    # keeps copy semantics for A/B tests and benchmarks
    return jax.jit(run, donate_argnums=(0,)) if cfg.donate else jax.jit(run)


@functools.lru_cache(maxsize=None)
def insert_chunk_batch_fn(cfg: SkyConfig,
                          mesh: jax.sharding.Mesh | None = None,
                          q_axis: str = "queries",
                          w_axis: str = "workers"):
    """Jitted ``(state, pts (Q, N, d), mask (Q, N), keys (Q, ...)) ->
    (state', stats)`` advancing Q live skylines in one dispatch. With a
    2-D mesh, Q must be a multiple of the ``q_axis`` size and cfg's
    partition count a multiple of the ``w_axis`` size."""

    def run(state, pts, mask, keys):
        par._TRACE_EVENTS["insert_batch"] += 1
        return _insert_batch(state, pts, mask, keys, cfg=cfg, mesh=mesh,
                             q_axis=q_axis, w_axis=w_axis)

    return jax.jit(run, donate_argnums=(0,)) if cfg.donate else jax.jit(run)


@functools.lru_cache(maxsize=None)
def finalize_fn(cfg: SkyConfig, batched: bool = False):
    """Jitted ``state -> SkyBuffer`` canonical snapshot (non-destructive:
    the state stays live and can keep absorbing chunks)."""
    fn = functools.partial(_finalize, cfg=cfg)
    return jax.jit(jax.vmap(fn) if batched else fn)


def insert_chunk(state: SkylineState, pts: jnp.ndarray,
                 mask: jnp.ndarray | None = None, *, cfg: SkyConfig,
                 key: jax.Array | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 axis_name: str = "workers"):
    """Convenience wrapper over `insert_chunk_fn` with defaulted mask/key.

    Dispatches the batched program when the state carries a leading Q axis
    (pts must then be (Q, N, d) and ``axis_name`` names the workers axis
    of a 2-D mesh)."""
    batched = state.points.ndim == 3
    if mask is None:
        mask = jnp.ones(pts.shape[:-1], jnp.bool_)
    if key is None:
        key = jax.random.PRNGKey(0)
    if batched:
        q = state.points.shape[0]
        keys = key if key.ndim == 2 else jax.random.split(key, q)
        return insert_chunk_batch_fn(cfg, mesh, w_axis=axis_name)(
            state, pts, mask, keys)
    return insert_chunk_fn(cfg, mesh, axis_name)(state, pts, mask, key)


def finalize(state: SkylineState, *, cfg: SkyConfig) -> SkyBuffer:
    """Canonical `SkyBuffer` snapshot of one or Q live skylines."""
    return finalize_fn(cfg, state.points.ndim == 3)(state)
