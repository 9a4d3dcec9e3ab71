"""Batched multi-query skyline engine.

The serving regime (ROADMAP north star: many concurrent users) is many
small/medium skyline queries, where per-query dispatch overhead dominates
the quadratic dominance work the paper parallelizes. The engine amortizes
that overhead: Q independent queries — separate datasets, or
preference-scaled views of one dataset — are padded to a common size
bucket, stacked, and answered with **one** invocation of the fused
partition+local+merge program, i.e. a single XLA dispatch for the whole
batch.

Dispatch is two-path. Small-query buckets go through plain
vmap-over-queries of the single-device program. When the engine holds a
2-D ``(queries, workers)`` mesh, buckets whose padded length reaches
``shard_threshold_n`` route through the sharded batch program
(`repro.core.parallel.fused_skyline_batch_fn`): the query batch is
sharded over the ``queries`` mesh axis and each query's partitions over
the ``workers`` axis, so large queries engage every device instead of
serializing on one. Both paths run identical comparison/selection math
and return bit-for-bit equal results.

Compilation-cache friendliness: query count Q and query length N are both
rounded up to power-of-two buckets (with floors), so the number of
distinct compiled programs is bounded by #Q-buckets x #N-buckets per
config, regardless of the ragged sizes users submit. Packing is
two-level: level 1 copies each ragged query into a host-side staging
buffer (exact ragged shapes never reach XLA), level 2 is one jitted
finalize per size bucket — so adversarial raggedness cannot grow the
compile cache beyond the bucket count (`pack_trace_count` observes this).
Padding rows and padding queries are fully masked out; every stage of the
pipeline is mask-correct, so results are identical to per-query
execution.

Streaming: `open_stream` returns a `SkylineStream` — Q live skylines
advanced with one `feed` dispatch per arriving chunk batch and snapshot
at any time via `snapshot()`, bit-for-bit equal to re-running the whole
(unexpired) history through `run`. Stream states live in the engine's
shared slab allocator (`repro.serve.slab`): one device-resident arena
per (d, dtype, epochs, slot-rows) bucket, tenants lease front-sized
slots, and gather+insert+scatter fuse into one jitted program per
bucket — device buffers are O(#buckets), never O(#streams). With
``window_epochs=E`` the streams are sliding windows over an epoch ring
(repro.core.windowed): `tick()` ages all Q windows in one O(1)
dispatch and `snapshot` merges the ring on read. Chunks go through the
same two-level host-staged pack, so the insert compile cache is bounded
by the chunk-size buckets, never by the exact ragged arrival sizes.

Typical use::

    engine = SkylineEngine(SkyConfig(strategy="sliced", p=8))
    buf, stats = engine.submit(SkylineRequest(data=pts))
    results = engine.submit_many(
        [SkylineRequest(data=pts_a),                  # ragged batch
         SkylineRequest(data=pts, scale=weights[0]),  # preference view
         SkylineRequest(data=pts, subspace=dims[0])])
    fronts = engine.member_masks([crit_a, crit_b])    # admission masks

    stream = engine.open_stream(4, StreamOptions(q=2))  # 2 live skylines
    stream.feed([chunk_a0, chunk_b0])                 # one dispatch
    stream.feed([chunk_a1, None])                     # ragged arrivals
    (buf_a, buf_b) = stream.snapshot()                # canonical fronts

    mesh = make_engine_mesh(queries=2, workers=4)     # 8 devices
    engine = SkylineEngine(cfg, mesh=mesh, shard_threshold_n=4096)

The legacy per-family entry points (``run`` / ``run_scaled`` /
``run_subspace``, and ``open_stream``'s loose keyword knobs) remain as
thin deprecated wrappers over the request API, bit-for-bit equal to
``submit_many`` on the same inputs.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import sys
import time
import warnings
from collections.abc import Mapping
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import incremental, windowed
from repro.core import parallel as par
from repro.core.dominance import SENTINEL
from repro.core.parallel import SkyConfig, fused_skyline_batch_fn
from repro.core.sfs import SkyBuffer
from repro.core.sfs import skyline_mask as _skyline_mask
from repro.kernels.backend import resolve_spec
from repro.serve.api import SkylineRequest, StreamOptions
from repro.serve.slab import SlabArena, blank_leaf, slot_rows_bucket

__all__ = ["SkylineEngine", "SkylineStream", "SkylineRequest",
           "StreamOptions", "pack_trace_count",
           "calibrate_shard_threshold"]


def _next_bucket(size: int, floor: int) -> int:
    """Smallest power of two >= max(size, floor)."""
    b = max(int(floor), 1)
    while b < size:
        b *= 2
    return b


def _round_up(size: int, multiple: int) -> int:
    return -(-size // multiple) * multiple


# --------------------------------------------------------------------------
# Two-level bucketed pack
# --------------------------------------------------------------------------

# Traced-callback counter for the level-2 pack programs, mirroring
# repro.core.parallel.trace_count(): tests assert the pack compile cache
# stays bounded by the number of size buckets under ragged streams.
_PACK_EVENTS: collections.Counter[str] = collections.Counter()


def pack_trace_count() -> int:
    """How many distinct pack programs have been traced (bounded by the
    number of (Q-bucket, N-bucket, dtype, masked) combinations — never by
    the exact ragged sizes submitted)."""
    return _PACK_EVENTS["pack"]


@functools.lru_cache(maxsize=None)
def _pack_fn(nb: int, qb: int, d: int, dtype: str, masked: bool):
    """Level 2 of the bucketed pack: one jitted finalize per size bucket.

    Level 1 (`SkylineEngine._pack`) copies each ragged query into a
    host-side (qb, nb, d) staging buffer, so the exact ragged lengths
    reach this program only as *data* (the ``lengths`` vector), never as
    shapes: the cache key is the bucket, and the number of compiled pack
    programs is bounded by the number of size buckets no matter how
    adversarially ragged the submitted sizes are.
    """

    def finalize(stacked, lengths, user_mask):
        _PACK_EVENTS["pack"] += 1
        valid = jnp.arange(nb)[None, :] < lengths[:, None]
        if masked:
            valid = valid & user_mask
        return stacked, valid

    if masked:
        return jax.jit(finalize)
    fn = jax.jit(lambda stacked, lengths: finalize(stacked, lengths, None))
    return lambda stacked, lengths, user_mask: fn(stacked, lengths)


@functools.lru_cache(maxsize=None)
def _view_pack_fn(nb: int, qb: int, d: int, dtype: str, masked: bool,
                  kind: str):
    """Level 2 of the bucketed pack for *stacked views* of one dataset
    (`run_scaled` / `run_subspace`): one jitted finalize per size bucket.

    Level 1 stages the shared dataset into a host-side (nb, d) buffer and
    the per-view parameters into a (qb, d) buffer, so the exact (Q, N)
    reach this program only as data (the ``n_len`` / ``q_len`` scalars) —
    the compile cache is bounded by the bucket count under ragged
    multi-tenant shapes, exactly like `_pack_fn` (the eager per-shape
    ``jnp.pad`` this replaces compiled one program per exact (Q, N)).
    """

    def finalize(staged, n_len, q_len, params, user_mask):
        _PACK_EVENTS["pack"] += 1
        valid = ((jnp.arange(nb)[None, :] < n_len)
                 & (jnp.arange(qb)[:, None] < q_len))
        if masked:
            valid = valid & user_mask[None, :]
        if kind == "scale":
            views = staged[None, :, :] * params[:, None, :]
        else:  # subspace: ignored attributes zeroed (non-discriminating)
            views = jnp.where(params[:, None, :].astype(bool),
                              staged[None, :, :], 0.0)
        return jnp.where(valid[:, :, None], views, SENTINEL), valid

    if masked:
        return jax.jit(finalize)
    fn = jax.jit(lambda s, n, q, p: finalize(s, n, q, p, None))
    return lambda s, n, q, p, user_mask: fn(s, n, q, p)


@functools.lru_cache(maxsize=None)
def _unpack_fn(q: int):
    """One jitted dispatch that splits a stacked result pytree into q
    per-query pytrees (XLA multi-output beats q x leaf gather calls)."""
    return jax.jit(lambda tree: tuple(
        jax.tree.map(lambda x: x[i], tree) for i in range(q)))


class _SlicedStats(Mapping):
    """Per-query view of a batch's stats pytree, sliced on access.

    Stats are read far less often than result buffers (debug/monitoring),
    so the engine defers the q x n_keys slice dispatches until a caller
    actually looks."""

    def __init__(self, stats: dict[str, jnp.ndarray], idx: int):
        self._stats = stats
        self._idx = idx

    def __getitem__(self, key):
        return self._stats[key][self._idx]

    def __iter__(self):
        return iter(self._stats)

    def __len__(self):
        return len(self._stats)


@functools.partial(jax.jit, static_argnames=("impl",))
def _batched_member_mask(pts, masks, impl: str = "auto"):
    return jax.vmap(lambda p, m: _skyline_mask(p, m, impl=impl))(pts, masks)


class SkylineEngine:
    """Answers batches of independent skyline queries in one dispatch.

    Args:
      cfg: pipeline configuration shared by all queries of this engine.
      min_n_bucket / min_q_bucket: floors of the power-of-two size
        buckets for query length and query count.
      mesh: optional 2-D device mesh carrying `q_axis` and `w_axis`
        (see `repro.launch.mesh.make_engine_mesh`). Without one, every
        bucket uses the pure vmap path.
      shard_threshold_n: padded query length at which a bucket routes
        through the 2-D sharded program instead of plain vmap. Small
        queries stay on the vmap path — below the threshold the
        collective overhead of sharding exceeds the dominance work it
        divides.
      q_axis / w_axis: mesh axis names for the query batch and the
        per-query tuple partitions.

    The engine is stateless between calls apart from counters
    (`queries_answered`, `batches_dispatched`, `sharded_dispatched`) and
    jax's compilation caches, so one engine can serve concurrent callers.
    ``cfg.impl`` is resolved once at construction into ``kernel_spec``
    (repro.kernels.backend), so an unknown backend fails fast here.
    """

    def __init__(self, cfg: SkyConfig = SkyConfig(), *,
                 min_n_bucket: int = 64, min_q_bucket: int = 4,
                 mesh: jax.sharding.Mesh | None = None,
                 shard_threshold_n: int = 4096,
                 q_axis: str = "queries", w_axis: str = "workers",
                 min_slab_rows: int = 64):
        if mesh is not None:
            missing = {q_axis, w_axis} - set(mesh.axis_names)
            if missing:
                raise ValueError(
                    f"mesh lacks engine axes {sorted(missing)}; "
                    f"has {mesh.axis_names}")
        # resolve the kernel backend once, up front: an unknown
        # `cfg.impl` fails at engine construction, not mid-dispatch
        self.kernel_spec = resolve_spec(cfg.impl)
        self.cfg = cfg
        self.min_n_bucket = min_n_bucket
        self.min_q_bucket = min_q_bucket
        self.mesh = mesh
        self.shard_threshold_n = shard_threshold_n
        self.q_axis = q_axis
        self.w_axis = w_axis
        self.min_slab_rows = min_slab_rows
        # per-bucket (queries x workers) mesh factorings, set by
        # `calibrate_shard_threshold(..., factorings=True)`: bucket nb ->
        # (qa, wa, merge-mode). Buckets without an entry use the
        # constructor mesh; the merge-mode column resolves cfg.merge ==
        # 'auto' per topology (flat all_gather union vs the log2(W)-round
        # pruning ppermute tree — see repro.core.parallel.merge_stage).
        self.factorings: dict[int, tuple[int, int, str]] = {}
        self._fact_meshes: dict[tuple[int, int], jax.sharding.Mesh] = {}
        # measured wave times from `calibrate_shard_threshold`, keyed
        # (d, dtype-name, n-bucket): seeds `ServeLoop`'s per-bucket
        # EWMA admission model so the first waves after startup are
        # admitted against data rather than a cold scalar
        self.wave_time_hints: dict[tuple, float] = {}
        # shared slab arenas: tenant stream states lease slots from ONE
        # device-resident arena per (d, dtype, epochs, slot-rows) bucket
        self._arenas: dict[tuple, SlabArena] = {}
        # calibrated kernel geometry (`repro.kernels.tuning`): set by
        # `calibrate_kernels(engine)`; None falls back to the process
        # default table (env REPRO_KERNEL_TUNING)
        self.kernel_tuning = None
        # union-size histogram: observed per-stream per-epoch front
        # sizes, keyed (d, epochs) -> Counter{size: occurrences}.
        # Recorded off the hot path (stream counters()/close()) and
        # consulted by `open_stream` to auto-size `epoch_capacity`
        # when the StreamOptions knob is left unset.
        self.epoch_front_hist: dict[tuple[int, int],
                                    collections.Counter] = {}
        self.queries_answered = 0
        self.batches_dispatched = 0
        self.sharded_dispatched = 0

    # -- dispatch planning -------------------------------------------------

    def _use_sharded(self, nb: int) -> bool:
        return self.mesh is not None and nb >= self.shard_threshold_n

    def _mesh_for(self, nb: int | None) -> jax.sharding.Mesh | None:
        """The 2-D mesh a size-``nb`` bucket routes through: the
        calibrated per-bucket factoring when one was measured
        (`calibrate_shard_threshold`), else the constructor mesh."""
        if self.mesh is None:
            return None
        fact = None if nb is None else self.factorings.get(nb)
        if fact is None:
            return self.mesh
        qw = fact[:2]
        m = self._fact_meshes.get(qw)
        if m is None:
            from repro.launch.mesh import make_engine_mesh
            m = make_engine_mesh(qw[0], qw[1], q_axis=self.q_axis,
                                 w_axis=self.w_axis)
            self._fact_meshes[qw] = m
        return m

    def _merge_mode_for(self, nb: int | None) -> str | None:
        """The calibrated merge topology of a bucket's factoring, or
        None when the bucket was never measured (cfg.merge == 'auto'
        then falls through to the modeled-bytes resolution inside
        `repro.core.parallel.merge_stage`)."""
        fact = None if nb is None else self.factorings.get(nb)
        return fact[2] if fact is not None and len(fact) > 2 else None

    def _q_bucket(self, q: int, sharded: bool, nb: int | None = None) -> int:
        """Padded query count: power-of-two bucket, and on the sharded
        path additionally a multiple of the queries-axis size."""
        floor = self.min_q_bucket
        if sharded:
            nq = self._mesh_for(nb).shape[self.q_axis]
            return _round_up(_next_bucket(q, max(floor, nq)), nq)
        return _next_bucket(q, floor)

    def _pipeline(self, sharded: bool, nb: int | None = None,
                  cfg: SkyConfig | None = None):
        cfg = self.cfg if cfg is None else cfg
        if sharded:
            if cfg.merge == "auto":
                mode = self._merge_mode_for(nb)
                if mode is not None:
                    cfg = dataclasses.replace(cfg, merge=mode)
            return fused_skyline_batch_fn(cfg, self._mesh_for(nb),
                                          self.q_axis, self.w_axis)
        return fused_skyline_batch_fn(cfg)

    def _cfg_for(self, impl: str | None, d: int | None = None,
                 dtype=None) -> SkyConfig:
        """The engine config with a per-request kernel-backend override
        applied (requests without one share `self.cfg`, and with it the
        compile cache), then the calibrated kernel geometry.

        The (block, wtile) tuning table (`repro.kernels.tuning`) is
        consulted only for what the user left open: ``cfg.impl`` must be
        'auto' with no per-request override, and ``cfg.wtile`` unset (an
        explicitly pinned tile always wins).  SkyConfig is value-equal,
        so two requests tuned to the same geometry share one compiled
        program."""
        cfg = self.cfg
        if impl is not None and impl != cfg.impl:
            resolve_spec(impl)
            return dataclasses.replace(cfg, impl=impl)
        if (cfg.impl == "auto" and cfg.wtile == 0 and d is not None):
            from repro.kernels.tuning import (check_platform,
                                              default_table, tuning_key)
            table = self.kernel_tuning or default_table()
            if table is not None:
                check_platform(table)
                entry = table.entries.get(
                    tuning_key("sweep", d, dtype or jnp.float32))
                if entry is not None and entry.bitwise_ok:
                    cfg = dataclasses.replace(cfg, block=entry.block,
                                              wtile=entry.wtile)
        return cfg

    # -- slab arenas -------------------------------------------------------

    def _arena(self, d: int, dtype, epochs: int, rows: int) -> SlabArena:
        """The shared arena for one (d, dtype, epochs, slot-rows) bucket
        — created on first use, then leased from by every stream of the
        bucket (device buffers stay O(#buckets), never O(#streams))."""
        key = (int(d), jnp.dtype(dtype).name, int(epochs), int(rows))
        arena = self._arenas.get(key)
        if arena is None:
            arena = self._arenas[key] = SlabArena(
                epochs=epochs, rows=rows, d=d, dtype=dtype,
                donate=self.cfg.donate)
        return arena

    def arena_report(self) -> dict[tuple, dict[str, int]]:
        """Per-bucket slab accounting (slots / leases / device buffers /
        bytes) — the O(#buckets) memory assertion reads this."""
        return {k: {"slots": a.capacity, "leased": a.leased,
                    "buffers": a.num_buffers(), "bytes": a.device_bytes(),
                    "grows": a.grows}
                for k, a in self._arenas.items()}

    # -- padding helpers ---------------------------------------------------

    def _group(self, items) -> dict[tuple, list[int]]:
        """Indices grouped by compatible batch key (d, dtype, N-bucket)."""
        groups: dict[tuple, list[int]] = {}
        for i, x in enumerate(items):
            n, d = x.shape
            kb = (d, jnp.dtype(x.dtype).name,
                  _next_bucket(n, self.min_n_bucket))
            groups.setdefault(kb, []).append(i)
        return groups

    def _pack(self, items, masks, idxs, qb: int):
        """Pad+stack the queries at `idxs` to (qb, nb, d) / (qb, nb).

        Level 1 of the bucketed pack: each query is copied into a numpy
        staging buffer at its exact length (a host-side memcpy — device
        queries sync once here), then a single bucket-keyed jitted
        finalize uploads the batch and builds the validity mask from the
        dynamic lengths vector. See `_pack_fn` for why this bounds the
        compile cache."""
        ns = [items[i].shape[0] for i in idxs]
        nb = _next_bucket(max(ns), self.min_n_bucket)
        d = items[idxs[0]].shape[1]
        dtype = jnp.dtype(items[idxs[0]].dtype)
        staged = np.full((qb, nb, d), SENTINEL, dtype)
        lengths = np.zeros((qb,), np.int32)
        any_masked = any(masks[i] is not None for i in idxs)
        user_mask = np.ones((qb, nb), bool) if any_masked else None
        for j, i in enumerate(idxs):
            staged[j, :ns[j]] = np.asarray(items[i])
            lengths[j] = ns[j]
            if any_masked and masks[i] is not None:
                user_mask[j, :ns[j]] = np.asarray(masks[i])
        return _pack_fn(nb, qb, d, dtype.name, any_masked)(
            staged, lengths, user_mask)

    def _keys_batch(self, keys, idxs, qb: int):
        """(qb, 2) stacked keys; `keys` is a (Q, 2) array or a list of
        PRNGKeys. Dummy padding queries get zero keys."""
        if isinstance(keys, jnp.ndarray) and keys.ndim == 2:
            sel = (keys if len(idxs) == keys.shape[0]
                   and list(idxs) == list(range(keys.shape[0]))
                   else keys[jnp.asarray(list(idxs))])
        else:
            sel = jnp.stack([keys[i] for i in idxs])
        pad = qb - len(idxs)
        if pad:
            sel = jnp.concatenate(
                [sel, jnp.zeros((pad,) + sel.shape[1:], sel.dtype)])
        return sel

    # -- main entry points (request-oriented) ------------------------------

    def submit(self, request: SkylineRequest,
               ) -> tuple[SkyBuffer, dict[str, Any]]:
        """Answer one `SkylineRequest` (see `submit_many`)."""
        return self.submit_many([request])[0]

    def submit_many(self, requests: Sequence[SkylineRequest],
                    ) -> list[tuple[SkyBuffer, dict[str, Any]]]:
        """Answer a mixed batch of `SkylineRequest`s, one (SkyBuffer,
        stats) each, in request order.

        Plain requests are grouped by (d, dtype, N-bucket, impl); each
        group becomes a single invocation of the batched pipeline —
        vmap-only for small buckets, the 2-D (queries x workers) sharded
        program for buckets at or above `shard_threshold_n` when the
        engine holds a mesh. View requests (``scale`` / ``subspace``)
        that share one ``data`` object stack their view parameters and
        go through the broadcast view pack, so Q views of one dataset
        stay a single dispatch. Whenever no bucket overflows, results
        bit-match per-query `parallel_skyline` (padding is masked out
        end to end); under bucket overflow both paths drop excess rows,
        and the per-query `bucket_overflow`/`overflow` flags report the
        condition either way.

        Requests without a ``key`` draw from one positional
        ``jax.random.split(PRNGKey(0), len(requests))`` default, so an
        all-plain, all-default batch is bit-for-bit the legacy
        ``run(queries)``. Deadlines are ignored here (the caller is
        already waiting) — the async serve loop enforces them.
        """
        reqs = list(requests)
        if not reqs:
            return []
        for r in reqs:
            if not isinstance(r, SkylineRequest):
                raise TypeError(f"submit_many wants SkylineRequest items, "
                                f"got {type(r).__name__}")
        out: list[tuple[SkyBuffer, dict[str, Any]] | None] = [None] * len(reqs)
        defaults = [None]

        def _key_for(i):
            if reqs[i].key is not None:
                return reqs[i].key
            if defaults[0] is None:
                defaults[0] = jax.random.split(jax.random.PRNGKey(0),
                                               len(reqs))
            return defaults[0][i]

        # plain requests, grouped by compatible batch key (+ backend)
        groups: dict[tuple, list[int]] = {}
        vgroups: dict[tuple, list[int]] = {}
        for i, r in enumerate(reqs):
            n, d = r.data.shape
            if r.view_kind is None:
                kb = (d, jnp.dtype(r.data.dtype).name,
                      _next_bucket(n, self.min_n_bucket), r.impl)
                groups.setdefault(kb, []).append(i)
            else:
                mk = id(r.mask) if r.mask is not None else None
                vgroups.setdefault((id(r.data), r.view_kind, mk, r.impl),
                                   []).append(i)
        for (d, dtn, nb, impl), idxs in groups.items():
            # pack (pad+stack, masked dummy queries fill the Q bucket —
            # the pipeline is exact on empty inputs), compute, and unpack
            # are one XLA dispatch each, so engine overhead stays O(1)
            # dispatches per batch rather than O(Q).
            sharded = self._use_sharded(nb)
            qb = self._q_bucket(len(idxs), sharded, nb)
            items = [reqs[i].data for i in idxs]
            masks = [reqs[i].mask for i in idxs]
            pts_b, mask_b = self._pack(items, masks, range(len(idxs)), qb)
            keys_b = self._keys_batch([_key_for(i) for i in idxs],
                                      range(len(idxs)), qb)
            bufs, stats = self._pipeline(
                sharded, nb, self._cfg_for(impl, d, dtn))(
                pts_b, mask_b, keys_b)
            self.batches_dispatched += 1
            self.sharded_dispatched += sharded
            per_query = _unpack_fn(qb)(bufs)
            for j, i in enumerate(idxs):
                out[i] = (per_query[j], _SlicedStats(stats, j))
        for (_, kind, _, impl), idxs in vgroups.items():
            r0 = reqs[idxs[0]]
            params = np.stack([np.asarray(reqs[i].scale if kind == "scale"
                                          else reqs[i].subspace)
                               for i in idxs])
            # the legacy all-default quirk (keys drawn per *bucket* row,
            # not per view) is preserved bit-for-bit for shim parity
            keys = (None if all(reqs[i].key is None for i in idxs)
                    else [_key_for(i) for i in idxs])
            res = self._run_stacked(
                r0.data, params, r0.mask, keys, kind,
                cfg=self._cfg_for(impl, r0.data.shape[1], r0.data.dtype))
            for j, i in enumerate(idxs):
                out[i] = res[j]
        self.queries_answered += len(reqs)
        return out  # type: ignore[return-value]

    def _run_stacked(self, pts: jnp.ndarray, params: jnp.ndarray,
                     mask: jnp.ndarray | None, keys, kind: str,
                     cfg: SkyConfig | None = None,
                     ) -> list[tuple[SkyBuffer, dict[str, Any]]]:
        """Q views of one (N, d) dataset through the two-level bucketed
        pack: the dataset and the (Q, d) view parameters are host-staged
        at their exact sizes, then one bucket-keyed jitted finalize
        builds the (qb, nb, d) view batch on device — the view broadcast
        and the padding are inside the same program, and the compile
        cache stays bounded by the size buckets no matter how ragged the
        submitted (Q, N) pairs are."""
        n, d = pts.shape
        q = params.shape[0]
        nb = _next_bucket(n, self.min_n_bucket)
        sharded = self._use_sharded(nb)
        qb = self._q_bucket(q, sharded, nb)
        dtype = jnp.dtype(pts.dtype)
        staged = np.full((nb, d), SENTINEL, dtype)
        staged[:n] = np.asarray(pts)
        params_b = np.zeros((qb, d),
                            np.bool_ if kind == "subspace" else dtype)
        params_b[:q] = np.asarray(params)
        user_mask = None
        if mask is not None:
            user_mask = np.zeros((nb,), bool)
            user_mask[:n] = np.asarray(jnp.broadcast_to(mask, (n,)))
        pts_b, mask_b = _view_pack_fn(nb, qb, d, dtype.name,
                                      mask is not None, kind)(
            staged, np.int32(n), np.int32(q), params_b, user_mask)
        if keys is None:
            keys_b = jax.random.split(jax.random.PRNGKey(0), qb)
        else:
            keys_b = self._keys_batch(keys, range(q), qb)
        bufs, stats = self._pipeline(sharded, nb, cfg)(pts_b, mask_b,
                                                       keys_b)
        self.batches_dispatched += 1
        self.sharded_dispatched += sharded
        per_query = _unpack_fn(qb)(bufs)
        return [(per_query[j], _SlicedStats(stats, j)) for j in range(q)]

    # -- legacy entry points (deprecated wrappers over the request API) ----

    def run(self, queries: Sequence[jnp.ndarray], *,
            masks: Sequence[jnp.ndarray | None] | None = None,
            keys: Sequence[jax.Array] | None = None,
            ) -> list[tuple[SkyBuffer, dict[str, Any]]]:
        """Deprecated: build `SkylineRequest`s and call `submit_many`.

        Kept as a thin wrapper (bit-for-bit equal to the request path,
        asserted by tests/test_serve_loop.py) for one release."""
        warnings.warn("SkylineEngine.run is deprecated; submit "
                      "SkylineRequest objects via submit()/submit_many()",
                      DeprecationWarning, stacklevel=2)
        q = len(queries)
        if q == 0:
            return []
        if masks is None:
            masks = [None] * q
        if keys is None:
            keys = jax.random.split(jax.random.PRNGKey(0), q)
        elif len(keys) != q:
            raise ValueError(f"got {len(keys)} keys for {q} queries")
        return self.submit_many([
            SkylineRequest(data=x, mask=m, key=keys[i])
            for i, (x, m) in enumerate(zip(queries, masks))])

    def run_scaled(self, pts: jnp.ndarray, weights: jnp.ndarray, *,
                   mask: jnp.ndarray | None = None,
                   keys: Sequence[jax.Array] | None = None,
                   ) -> list[tuple[SkyBuffer, dict[str, Any]]]:
        """Deprecated: Q preference-scaled views of one dataset
        (``weights`` is (Q, d) positive per-attribute scales); submit
        `SkylineRequest(data=pts, scale=w)` instead. The wrapper builds
        the requests — sharing one ``data`` object, so they stack into
        the same single broadcast dispatch as before."""
        warnings.warn("SkylineEngine.run_scaled is deprecated; submit "
                      "SkylineRequest(data=..., scale=...) via "
                      "submit()/submit_many()",
                      DeprecationWarning, stacklevel=2)
        if weights.ndim != 2 or weights.shape[1] != pts.shape[1]:
            raise ValueError("weights must be (Q, d)")
        return self._legacy_views(pts, weights, mask, keys, "scale")

    def run_subspace(self, pts: jnp.ndarray, dim_masks: jnp.ndarray, *,
                     mask: jnp.ndarray | None = None,
                     keys: Sequence[jax.Array] | None = None,
                     ) -> list[tuple[SkyBuffer, dict[str, Any]]]:
        """Deprecated: Q subspace-skyline views of one dataset
        (``dim_masks`` is (Q, d) bool; ignored attributes are zeroed,
        making them non-discriminating); submit
        `SkylineRequest(data=pts, subspace=m)` instead."""
        warnings.warn("SkylineEngine.run_subspace is deprecated; submit "
                      "SkylineRequest(data=..., subspace=...) via "
                      "submit()/submit_many()",
                      DeprecationWarning, stacklevel=2)
        if dim_masks.ndim != 2 or dim_masks.shape[1] != pts.shape[1]:
            raise ValueError("dim_masks must be (Q, d) bool")
        return self._legacy_views(pts, dim_masks, mask, keys, "subspace")

    def _legacy_views(self, pts, params, mask, keys, kind: str):
        rows = np.asarray(params)
        if keys is not None and len(keys) != rows.shape[0]:
            raise ValueError(f"got {len(keys)} keys for {rows.shape[0]} "
                             f"views")
        return self.submit_many([
            SkylineRequest(data=pts, mask=mask,
                           scale=rows[i] if kind == "scale" else None,
                           subspace=rows[i] if kind == "subspace" else None,
                           key=None if keys is None else keys[i])
            for i in range(rows.shape[0])])

    def member_masks(self, crits: Sequence[jnp.ndarray], *,
                     masks: Sequence[jnp.ndarray | None] | None = None,
                     ) -> list[jnp.ndarray]:
        """Skyline *membership masks* (input order) for Q criteria sets.

        The scheduler's admission path needs in-place membership, not the
        compacted buffer; this batches `skyline_mask` with the same
        padding/bucketing scheme.
        """
        q = len(crits)
        if q == 0:
            return []
        if masks is None:
            masks = [None] * q
        out: list[jnp.ndarray | None] = [None] * q
        for (d, _, nb), idxs in self._group(crits).items():
            qb = _next_bucket(len(idxs), self.min_q_bucket)
            pts_b, mask_b = self._pack(crits, masks, idxs, qb)
            res = _batched_member_mask(pts_b, mask_b, impl=self.cfg.impl)
            self.batches_dispatched += 1
            for j, i in enumerate(idxs):
                out[i] = res[j, :crits[i].shape[0]]
        self.queries_answered += q
        return out  # type: ignore[return-value]

    # -- streaming ---------------------------------------------------------

    def record_epoch_fronts(self, d: int, epochs: int, counts) -> None:
        """Fold observed per-epoch front sizes into the union-size
        histogram.  ``counts`` is the (q, epochs) per-epoch antichain
        sizes a stream's `counters`/`close` sync materialized; zero
        entries (never-opened ring slots) carry no sizing information
        and are dropped."""
        sizes = np.asarray(counts).reshape(-1)
        sizes = sizes[sizes > 0]
        if sizes.size == 0:
            return
        hist = self.epoch_front_hist.setdefault(
            (int(d), int(epochs)), collections.Counter())
        hist.update(int(s) for s in sizes)

    def suggest_epoch_capacity(self, d: int, epochs: int) -> int:
        """Data-derived ``epoch_capacity`` for a new (d, epochs)
        windowed stream, from the union-size histogram — 0 when there
        is no basis for a suggestion (measure, don't guess: fewer than
        8 observed epoch fronts means the default full-capacity slots
        stand).

        The suggestion is 2x the largest front ever observed for the
        bucket (headroom for drift), rounded up to the dominance block
        so the slot shape is a kernel-friendly one, and only returned
        at all when it actually shrinks the slots below the full state
        capacity."""
        hist = self.epoch_front_hist.get((int(d), int(epochs)))
        if hist is None or sum(hist.values()) < 8:
            return 0
        block = self.cfg.block
        sug = -(-2 * max(hist) // block) * block
        if sug >= incremental.state_capacity(self.cfg):
            return 0
        return sug

    def open_stream(self, d: int, options: StreamOptions | None = None,
                    **legacy) -> "SkylineStream":
        """Open ``options.q`` live skylines over ``d``-attribute tuples.

        All stream knobs travel in a validated `StreamOptions`
        (`repro.serve.api`); passing them as loose keywords (``q=``,
        ``window_epochs=``, ...) still works but is deprecated.

        The returned `SkylineStream` keeps its states in the engine's
        shared slab arena (one device-resident arena per bucket, leased
        slots per tenant — `repro.serve.slab`); every `feed` is one
        insert dispatch for all q streams, routed through the same
        vmap-vs-sharded policy as `submit_many` (chunk buckets at or
        above `shard_threshold_n` shard over the 2-D mesh).

        With ``window_epochs=E`` the streams are *sliding windows*: an
        epoch ring of E sub-states per stream (repro.core.windowed).
        ``stream.tick()`` opens a new epoch — per tenant or for every
        stream — in one dispatch (expiring the oldest epoch in O(1)
        once a tenant's ring is full) and `snapshot` merges the ring on
        read. Without it the window is unbounded (insert-only).

        ``epoch_capacity`` (windowed streams only) declares the
        expected per-epoch front size: slots are then sized and padded
        to it (rounded to the dominance block) instead of the full
        state capacity inside the fused feed — `repro.core.windowed`'s
        epoch-ring capacity semantics, now on the slab path too."""
        if legacy:
            if options is not None:
                raise ValueError("pass either a StreamOptions or legacy "
                                 "keywords, not both")
            unknown = set(legacy) - {"q", "dtype", "key", "window_epochs",
                                     "epoch_capacity"}
            if unknown:
                raise TypeError(f"open_stream got unexpected keywords "
                                f"{sorted(unknown)}")
            warnings.warn("open_stream(**knobs) is deprecated; pass "
                          "open_stream(d, StreamOptions(...))",
                          DeprecationWarning, stacklevel=2)
            options = StreamOptions(**legacy)
        elif options is None:
            options = StreamOptions()
        # the union-size histogram closes the sizing loop: a windowed
        # stream that left `epoch_capacity` unset gets the data-derived
        # suggestion (0 — i.e. full-capacity slots — until enough epoch
        # fronts of this (d, epochs) bucket have been observed)
        if options.window_epochs is not None and not options.epoch_capacity:
            sug = self.suggest_epoch_capacity(d, options.window_epochs)
            if sug:
                options = dataclasses.replace(options, epoch_capacity=sug)
        return SkylineStream(self, d=d, options=options)


# --------------------------------------------------------------------------
# Slab-fused stream programs: gather leased slots + insert + scatter the
# packed fronts back, ONE jitted dispatch per feed (and one per tick /
# snapshot), cached per bucket key — never per stream.
# --------------------------------------------------------------------------

def _gather_slots(leaves, idx):
    return tuple(a[idx] for a in leaves)


def _sub_of_epoch(gathered, heads, c: int):
    """The (B, rows)-packed per-slot target-epoch sub-states of gathered
    slots as a full-capacity batched `SkylineState` (rows padded to
    ``c``). ``heads`` is a traced (B,) epoch vector — per-tenant ring
    clocks — so one compiled program serves every mix of head
    positions."""
    take = jax.vmap(functools.partial(jax.lax.dynamic_index_in_dim,
                                      axis=0, keepdims=False))
    sub = incremental.SkylineState(*(take(a, heads) for a in gathered))
    points, mask = incremental._fit_rows(sub.points, sub.mask, c)
    return sub._replace(points=points, mask=mask)


def _put_epoch(gathered, sub: incremental.SkylineState, heads, rows: int):
    """Write a batched sub-state back into each slot's ``heads[i]`` ring
    slot, truncated to the slot's ``rows`` (callers guarantee the packed
    fronts fit — see the promotion path)."""
    sub = sub._replace(points=sub.points[:, :rows],
                       mask=sub.mask[:, :rows])
    put = jax.vmap(
        lambda a, v, h: jax.lax.dynamic_update_index_in_dim(a, v, h, 0))
    return tuple(put(a, v, heads)
                 for a, v in zip(gathered, tuple(sub)))


def _splice_pending(fitted, pend_leaves, pos, sel, eps):
    """Overlay a pending wave's per-slot inserted epoch states onto
    gathered slot leaves: for each slot with ``sel[i]``, the pending row
    ``pos[i]`` replaces ring slot ``eps[i]``. The pending state is the
    authoritative value for its (slot, epoch) whether or not the
    conditional scatter installed it — when it fit, the arena copy is
    bitwise the same content, so the overlay is idempotent."""
    psub = incremental.SkylineState(*(a[pos] for a in pend_leaves))
    c = fitted[0].shape[-2]
    p_pts, p_mask = incremental._fit_rows(psub.points, psub.mask, c)
    psub = psub._replace(points=p_pts, mask=p_mask)

    def splice(leaf, val):
        upd = jax.vmap(lambda a, v, e:
                       jax.lax.dynamic_update_index_in_dim(a, v, e, 0))(
            leaf, val, eps)
        return jnp.where(sel.reshape((-1,) + (1,) * (leaf.ndim - 1)),
                         upd, leaf)

    return tuple(splice(a, v) for a, v in zip(fitted, tuple(psub)))


@functools.lru_cache(maxsize=None)
def _slab_feed_fn(cfg: SkyConfig, rows: int, q: int,
                  mesh: jax.sharding.Mesh | None,
                  q_axis: str, w_axis: str, cap: int,
                  npend: int = 0):
    """One fused wave program per bucket: gather the leased slots of one
    or MORE streams sharing the bucket, run the batched per-tenant
    head-epoch insert, and scatter the packed fronts back — per slot
    conditionally, so a front outgrowing its ``rows`` slot leaves the
    arena untouched and the returned ``cap``-row state (the wave's
    *pending* record) drives the fully-async promotion path instead.
    ``q`` is the wave's tenant count (only the first q of the padded
    slot indices are written); ``cap`` is the epoch-slot row ceiling
    (`windowed.epoch_rows` — the full state capacity for unbounded
    streams), so windowed feeds with a declared ``epoch_capacity``
    never pad slots back to the full C rows inside the fused program.

    ``npend`` is the number of unresolved pending records chained into
    the wave: each is overlaid on the gathered head-epoch states before
    inserting, restricted per entry to the tenants whose recorded ring
    slot IS the head this feed inserts into (entries parked at other
    epochs stay pending and keep overlaying reads — they are simply not
    part of this feed's target epoch). This is what lets feeds chain on
    overflowing feeds — any number of them, at any ring position —
    without any host read of a deferred ``fits`` vector: alive record
    entries are disjoint per (slot, epoch) (a chained wave kills the
    superseded head entries), so overlay order is immaterial."""

    def run(leaves, idx, heads, pts, mask, keys, *pargs):
        par._TRACE_EVENTS["slab_feed"] += 1
        gathered = _gather_slots(leaves, idx)
        sub = _sub_of_epoch(gathered, heads, cap)
        for r in range(npend):
            p_leaves, p_pos, p_sel, p_eps = pargs[4 * r:4 * r + 4]
            psub = incremental.SkylineState(
                *(a[p_pos] for a in p_leaves))
            p_pts, p_mask = incremental._fit_rows(psub.points, psub.mask,
                                                  cap)
            psub = psub._replace(points=p_pts, mask=p_mask)
            sel = p_sel & (p_eps == heads)
            sub = incremental.SkylineState(*(
                jnp.where(sel.reshape((-1,) + (1,) * (a.ndim - 1)),
                          pa, a)
                for a, pa in zip(tuple(sub), tuple(psub))))
        sub2, stats = incremental._insert_batch(
            sub, pts, mask, keys, cfg=cfg, mesh=mesh, q_axis=q_axis,
            w_axis=w_axis)
        # a slot at the epoch-capacity ceiling can never outgrow it;
        # otherwise each tenant checks its own front (per-slot fits)
        fits = (jnp.ones((q,), jnp.bool_) if rows >= cap
                else sub2.count[:q] <= rows)
        updated = _put_epoch(gathered, sub2, heads, rows)
        out = tuple(
            a.at[idx[:q]].set(
                jnp.where(fits.reshape((q,) + (1,) * (a.ndim - 1)),
                          u[:q], g[:q]))
            for a, u, g in zip(leaves, updated, gathered))
        return out, sub2, fits, stats

    # the arena leaves are donated (single-owner: `_wave_feed` hands them
    # over via arena.leaves() and installs the aliased outputs with
    # set_leaves); the pending-record operands (*pargs) are NOT — their
    # sub-states are shared with snapshot/counters overlays until resolved
    return jax.jit(run, donate_argnums=(0,)) if cfg.donate else jax.jit(run)


@functools.lru_cache(maxsize=None)
def _slab_promote_fn(old_rows: int, new_rows: int, q: int):
    """Move q streams' slots to a bigger rows bucket: re-pad the old
    slot contents and splice in the pending wave's inserted epoch
    states (the full-``cap``-row results the per-slot conditional
    scatter withheld) at each tenant's recorded epoch. Returns the
    (q, E, new_rows, ...) slot values for the new arena."""

    def run(old_leaves, idx, eps, sub_leaves, pos, take):
        gathered = _gather_slots(old_leaves, idx)  # (q, E, old_rows, ..)
        points, mask = incremental._fit_rows(gathered[0], gathered[1],
                                             new_rows)
        gathered = (points, mask) + gathered[2:]
        sub = incremental.SkylineState(*(a[pos] for a in sub_leaves))
        spliced = _put_epoch(gathered, sub, eps, new_rows)
        return tuple(
            jnp.where(take.reshape((-1,) + (1,) * (s.ndim - 1)), s, g)
            for s, g in zip(spliced, gathered))

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _slab_put_fn(q: int, donate: bool = True):
    def run(leaves, idx, vals):
        return tuple(a.at[idx].set(v) for a, v in zip(leaves, vals))
    return jax.jit(run, donate_argnums=(0,)) if donate else jax.jit(run)


@functools.lru_cache(maxsize=None)
def _slab_clear_epoch_fn(donate: bool = True):
    """Blank one epoch ring slot PER TENANT of a batch of leased slots
    (the O(1) expiry: nothing is recomputed, merge-on-read resolves the
    rest). ``epoch`` is a (q,) per-tenant slot vector and ``sel`` a
    (q,) bool mask — tenants outside the selection keep their ring
    untouched, so per-tenant clocks tick independently in one
    dispatch."""

    def run(leaves, idx, epoch, sel):
        par._TRACE_EVENTS["slab_tick"] += 1
        out = []
        for a in leaves:
            sub = a[idx]  # (q, E, ...)
            blank = blank_leaf(sub.shape[:1] + sub.shape[2:], a.dtype)
            upd = jax.vmap(lambda s, b, e:
                           jax.lax.dynamic_update_index_in_dim(s, b, e, 0)
                           )(sub, blank, epoch)
            upd = jnp.where(sel.reshape((-1,) + (1,) * (upd.ndim - 1)),
                            upd, sub)
            out.append(a.at[idx].set(upd))
        return tuple(out)

    return jax.jit(run, donate_argnums=(0,)) if donate else jax.jit(run)


@functools.lru_cache(maxsize=None)
def _slab_snapshot_fn(cfg: SkyConfig, rows: int, epochs: int,
                      npend: int = 0):
    """Canonical per-stream snapshot of leased slots in one dispatch:
    unbounded streams (E == 1) canonicalize their antichain directly;
    windowed streams merge the epoch ring on read (repro.core.windowed).
    The stream's ``npend`` unresolved pending wave records are overlaid
    first (`_splice_pending`, one per record — alive entries are
    disjoint per (slot, epoch), so order is immaterial), so a snapshot
    straight after an overflowing feed reads the true fronts WITHOUT
    any host-blocking resolve — the promotion decision keeps riding the
    async path."""
    c = incremental.state_capacity(cfg)

    def run(leaves, idx, *pargs):
        par._TRACE_EVENTS["slab_snapshot"] += 1
        gathered = _gather_slots(leaves, idx)
        points, mask = incremental._fit_rows(gathered[0], gathered[1], c)
        fitted = (points, mask) + gathered[2:]
        for r in range(npend):
            fitted = _splice_pending(fitted, *pargs[4 * r:4 * r + 4])
        points, mask, count, overflow, seen, chunks = fitted
        if epochs == 1:
            state = incremental.SkylineState(
                points[:, 0], mask[:, 0], count[:, 0], overflow[:, 0],
                seen[:, 0], chunks[:, 0])
            return jax.vmap(
                functools.partial(incremental._finalize, cfg=cfg))(state)
        wstate = windowed.WindowedSkylineState(
            points, mask, count, overflow, seen, chunks,
            head=jnp.int32(0), active=jnp.int32(epochs))
        return windowed._wfinalize_batch(wstate, cfg=cfg, mesh=None,
                                         q_axis="queries")

    # read-only overlay: the snapshot reads the live arena (and the
    # shared pending sub-states) that the next wave still consumes —
    # donating here would delete buffers another program owns
    # skylint: disable=R6
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _slab_counters_fn(npend: int = 0):
    """Per-stream running stats over the live ring in one dispatch,
    pending-overlay-aware like the snapshot program."""

    def run(leaves, idx, *pargs):
        gathered = _gather_slots(leaves, idx)
        for r in range(npend):
            gathered = _splice_pending(gathered, *pargs[4 * r:4 * r + 4])
        _, _, count, overflow, seen, chunks = gathered
        # the raw (q, epochs) per-epoch antichain sizes ride along: the
        # engine's epoch-front histogram (auto-sized `epoch_capacity`)
        # feeds off them at the same single host sync
        return (jnp.sum(count, axis=1), jnp.sum(seen, axis=1),
                jnp.sum(chunks, axis=1), jnp.any(overflow, axis=1),
                count)

    # read-only overlay: stats ride the live arena + shared pending
    # sub-states without consuming them (same contract as the snapshot)
    # skylint: disable=R6
    return jax.jit(run)


class _Pending:
    """One wave's deferred slot-overflow record.

    The wave program returns the full-``cap``-row inserted head-epoch
    states (``sub``) and a per-slot device ``fits`` vector; nothing on
    the host ever *waits* for them. ``pos`` maps this stream's tenants
    into the wave arrays, ``epochs`` snapshots each tenant's ring slot
    at feed time, and ``alive`` tracks which entries are still the
    authoritative value for their (slot, epoch) — a tick that clears
    the recorded slot kills the entry, and a chained feed into the
    same slot supersedes it. A stream may hold several records at once
    (``SkylineStream._pendings``) — one per unresolved wave — with
    alive entries disjoint per (slot, epoch). Until the non-blocking
    poll (`SkylineStream._maybe_resolve`) finds a record's ``fits``
    ready, every read and every chained feed overlays it inside its
    jitted program; no serving operation ever blocks on the check."""

    __slots__ = ("sub", "fits", "pos", "epochs", "alive")

    def __init__(self, sub, fits, pos, epochs, alive):
        self.sub = sub
        self.fits = fits
        self.pos = pos
        self.epochs = epochs
        self.alive = alive


class _WaveStats(Mapping):
    """Per-stream view of a wave's stats pytree: rows [off, off+q) of
    each leaf, sliced on access (stats are read far less often than
    result buffers, so the slices stay deferred)."""

    def __init__(self, stats: dict[str, jnp.ndarray], off: int, q: int):
        self._stats = stats
        self._off = off
        self._q = q

    def __getitem__(self, key):
        return self._stats[key][self._off:self._off + self._q]

    def __iter__(self):
        return iter(self._stats)

    def __len__(self):
        return len(self._stats)


def _wave_feed(engine: SkylineEngine, parts) -> Mapping:
    """ONE coalesced gather+insert+scatter dispatch for the feeds of
    one or more `SkylineStream`s sharing a slab bucket (``parts`` is a
    list of (stream, items, masks)).

    This is the cross-tenant coalescing primitive of the serve loop:
    the members' chunks go through the level-1 host pack together, the
    slot indices / per-tenant ring heads concatenate into one wave, and
    the per-stream partitioning keys are derived exactly as the serial
    feed derives them — so a coalesced wave is bit-for-bit equal to
    feeding the members one by one. Each member's share of the wave's
    deferred fits record becomes its `_Pending`; the host never reads
    the device between waves (an async host copy of ``fits`` is merely
    *started* so the later poll finds it ready)."""
    for s, _, _ in parts:
        s._maybe_resolve()
    groups: dict[tuple, list] = {}
    for part in parts:
        s = part[0]
        groups.setdefault((id(s.arena), s.rows, s.cap), []).append(part)
    if len(groups) > 1:
        # an opportunistic promotion just split the bucket: dispatch
        # each sub-bucket as its own wave
        stats = None
        for group in groups.values():
            stats = _wave_feed(engine, group)
        return stats
    s0 = parts[0][0]
    arena, rows, cap = s0.arena, s0.rows, s0.cap
    total = sum(p[0].q for p in parts)
    wb = engine._q_bucket(total, engine.mesh is not None)
    items: list = []
    masks: list = []
    idx: list[int] = []
    heads: list[int] = []
    key_rows = []
    for s, its, ms in parts:
        items += its
        masks += ms
        idx += list(map(int, s._idx()))  # raises if the stream closed
        heads += [int(h) for h in s._head]
        # per-stream key derivation matches the serial feed bit-for-bit
        key_rows.append(jax.random.split(
            jax.random.fold_in(jnp.asarray(s._key), s.chunks_fed),
            s.qb)[:s.q])
    pts_b, mask_b = engine._pack(items, masks, range(total), wb)
    nb = pts_b.shape[1]
    sharded = engine._use_sharded(nb)
    keys_b = (key_rows[0] if len(key_rows) == 1
              else jnp.concatenate(key_rows))
    pad = wb - total
    if pad:
        keys_b = jnp.concatenate(
            [keys_b, jnp.zeros((pad,) + keys_b.shape[1:], keys_b.dtype)])
    # chain EVERY unresolved record of every member into the program —
    # the wave-chaining fast path: a second (third, ...) overflow of the
    # same slab slot inside one in-flight window overlays the live
    # record per entry, and records parked at non-head epochs by a tick
    # simply ride along untouched. Records shared by several members
    # (from an earlier coalesced wave) are deduped by their fits buffer
    # and enter the program once, with the members' entries merged.
    recs: dict[int, tuple[tuple, list]] = {}
    off = 0
    for s, _, _ in parts:
        for p in s._pendings:
            if p.alive.any():
                recs.setdefault(id(p.fits), (tuple(p.sub), []))[1].append(
                    (off, s.q, p))
        off += s.q
    pargs: list = []
    for sub, members in recs.values():
        p_pos = np.zeros((wb,), np.int32)
        p_sel = np.zeros((wb,), bool)
        p_eps = np.zeros((wb,), np.int32)
        for off_s, sq, p in members:
            p_pos[off_s:off_s + sq] = p.pos
            p_sel[off_s:off_s + sq] = p.alive
            p_eps[off_s:off_s + sq] = p.epochs
        pargs += [sub, p_pos, p_sel, p_eps]
    fn = _slab_feed_fn(engine.cfg, rows, total,
                       engine.mesh if sharded else None, engine.q_axis,
                       engine.w_axis, cap, len(recs))
    idx_np = np.asarray(idx + [idx[0]] * pad, np.int32)
    heads_np = np.asarray(heads + [heads[0]] * pad, np.int32)
    new_leaves, sub2, fits, stats = fn(arena.leaves(), idx_np, heads_np,
                                       pts_b, mask_b, keys_b, *pargs)
    arena.set_leaves(new_leaves)
    sub2 = tuple(sub2)
    if rows < cap:
        # start the deferred per-slot fits on its way to the host so
        # the later non-blocking poll finds it ready
        fits.copy_to_host_async()
    off = 0
    for s, _, _ in parts:
        # this wave's write supersedes the chained head-epoch entries:
        # whether the scatter installed it or the new record carries it,
        # the old records are no longer authoritative for the head slot
        for p in s._pendings:
            p.alive &= ~(p.epochs == s._head)
        s._pendings = [p for p in s._pendings if p.alive.any()]
        if rows < cap:
            s._pendings.append(_Pending(
                sub=sub2, fits=fits,
                pos=np.arange(off, off + s.q, dtype=np.int32),
                epochs=s._head.copy(),
                alive=np.ones((s.q,), bool)))
        s.last_stats = _WaveStats(stats, off, s.q)
        s.chunks_fed += 1
        off += s.q
    engine.batches_dispatched += 1
    engine.sharded_dispatched += sharded
    return stats


class SkylineStream:
    """Q live skylines fed incrementally through a `SkylineEngine`.

    Arriving chunks are ragged per stream and per feed; they go through
    the engine's two-level host-staged pack into (qb, nb) size buckets,
    so both the pack and the insert compile caches stay bounded by the
    bucket count no matter how chunk sizes drift.

    States live in the engine's shared slab arena (`repro.serve.slab`):
    the stream leases one slot per live skyline from the arena of its
    (d, dtype, epochs, slot-rows) bucket, so a fleet of tenant streams
    shares O(#buckets) device buffers and each tenant's resident
    footprint is its slot's row count — a power-of-two tracking its
    *front* size, promoted to the next bucket when the front outgrows it
    — not the engine's full C-row state capacity. Every `feed` fuses
    gather + insert + scatter into one dispatch (and the serve loop
    coalesces feeds of multiple streams sharing a bucket into one wave
    — `_wave_feed`); `snapshot` returns canonical per-stream
    `SkyBuffer`s bit-for-bit equal to one-shot recomputation over the
    unexpired history (repro.core.incremental / repro.core.windowed).

    NO stream operation blocks on the device. When a front outgrows its
    slot, the wave program withholds that slot's scatter and returns
    the full inserted state as a *pending record*; reads and chained
    feeds overlay the record inside their jitted programs, and the
    stream is promoted to a bigger rows bucket only once a non-blocking
    poll finds the deferred per-slot ``fits`` vector already delivered
    (`_maybe_resolve`; `drain()` is the explicit blocking settle for
    shutdown and tests).

    With ``window_epochs=E`` the streams are sliding windows over an
    epoch ring: `tick()` opens a new epoch — for all q tenants or any
    subset — in one dispatch (a full ring expires its oldest epoch in
    O(1)), `expire_epoch()` drops tails without opening one, and
    `snapshot` merges the ring on read. Each tenant has its OWN ring
    clock (head/active vectors, host-side) — the clocks enter the
    compiled programs as data, so one compiled feed serves every mix of
    head positions.
    """

    def __init__(self, engine: SkylineEngine, *, d: int,
                 options: StreamOptions | None = None):
        if options is None:
            options = StreamOptions()
        self.engine = engine
        self.options = options
        self.q = options.q
        self.d = d
        self.dtype = jnp.dtype(options.dtype)
        self.window_epochs = options.window_epochs
        self.epochs = int(options.window_epochs or 1)
        self.epoch_capacity = int(options.epoch_capacity)
        # fixed Q bucket compatible with BOTH dispatch paths: with a mesh
        # it is a multiple of the queries-axis size, so any chunk bucket
        # may route sharded without reshaping the state
        self.qb = engine._q_bucket(self.q, engine.mesh is not None)
        # the slot-row ceiling: epoch_capacity (rounded to the dominance
        # block) for windowed streams that declared one, else the full
        # state capacity — promotions stop at it, and the fused feed
        # pads slots only up to it
        self.cap = windowed.epoch_rows(engine.cfg, self.epoch_capacity)
        self.rows = slot_rows_bucket(1, engine.min_slab_rows, self.cap)
        self.arena = engine._arena(d, self.dtype, self.epochs, self.rows)
        self.slots = self.arena.lease(self.q)
        # previous waves' deferred per-slot fits records (oldest first),
        # settled asynchronously — see `_maybe_resolve`. Alive entries
        # are disjoint per (slot, epoch): a chained feed kills the
        # superseded head entries, a tick kills the cleared slot's.
        self._pendings: list[_Pending] = []
        # per-tenant ring clocks (host-side int vectors; traced as
        # data, never as shapes)
        self._head = np.zeros((self.q,), np.int32)
        self._active = np.ones((self.q,), np.int32)
        # the seed key is stored host-side (an idle stream must hold NO
        # device buffers — np.asarray would alias the jax buffer and
        # keep it alive, so copy). New-style typed keys are stored as
        # their raw bits and re-derived through the legacy impl — keys
        # only seed the partitioning here, any deterministic stream is
        # valid.
        key = options.key
        if key is None:
            self._key = np.zeros((2,), np.uint32)
        else:
            if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                key = jax.random.key_data(key)
            self._key = np.array(key, copy=True)
        self.chunks_fed = 0
        self.ticks = 0
        self.last_stats: Mapping | None = None

    @property
    def windowed(self) -> bool:
        return self.window_epochs is not None

    def _idx(self, padded: bool = False) -> np.ndarray:
        if not self.slots:
            raise ValueError("stream is closed (slots released)")
        slots = self.slots
        if padded:  # fill the Q bucket by repeating slot 0 (reads only)
            slots = slots + [slots[0]] * (self.qb - self.q)
        return np.asarray(slots, np.int32)

    def _tenant_sel(self, tenants) -> np.ndarray:
        if tenants is None:
            return np.ones((self.q,), bool)
        sel = np.zeros((self.q,), bool)
        for t in tenants:
            t = int(t)
            if not 0 <= t < self.q:
                raise ValueError(f"tenant {t} out of range for "
                                 f"q={self.q}")
            sel[t] = True
        if not sel.any():
            raise ValueError("need at least one tenant")
        return sel

    def _pend_args(self) -> tuple:
        """Flattened (pend leaves, pos, sel, epochs) program arguments,
        four per unresolved pending record (may be empty)."""
        out: list = []
        for p in self._pendings:
            if p.alive.any():
                out += [tuple(p.sub), p.pos, p.alive, p.epochs]
        return tuple(out)

    # -- async pending settlement ------------------------------------------

    def _maybe_resolve(self) -> None:
        """Settle deferred per-slot fits checks WITHOUT blocking: the
        wave program computes ``fits`` on device and `_wave_feed`
        starts an async host copy; this poll settles exactly the
        records whose vector the device has delivered on its own
        (records resolve independently — their alive entries are
        disjoint per (slot, epoch)). Until then, every read and every
        chained feed overlays the records inside its jitted program —
        no stream operation ever waits on the check (the suppressed R1
        host sync this replaces is retired)."""
        for p in list(self._pendings):
            if not p.alive.any():
                self._pendings.remove(p)
            elif p.fits.is_ready():
                self._finish_resolve(p)

    def poll(self) -> bool:
        """Public non-blocking maintenance poll: settle any pending
        record whose deferred ``fits`` vector the device has already
        delivered, releasing the record (and the full-capacity
        sub-state it keeps alive) eagerly instead of at the next
        stream op. Returns True while records remain — callers (the
        serve loop's idle tick) keep polling until the list drains."""
        self._maybe_resolve()
        return bool(self._pendings)

    def _force_resolve(self) -> None:
        """Blocking settle of every outstanding record — the sanctioned
        host sync, reached only from `drain`, never from a serving
        operation (feed chains records instead)."""
        while self._pendings:
            self._finish_resolve(self._pendings[0])

    def _finish_resolve(self, pend: _Pending) -> None:
        self._pendings.remove(pend)
        if not pend.alive.any():
            return
        fits = np.asarray(pend.fits)[pend.pos]
        bad = pend.alive & ~fits
        if bad.any():
            # some front outgrew its slot: splice the withheld states
            # into a rows bucket holding the largest such front (the
            # per-slot conditional scatter left those arena slots
            # untouched). Other records stay pending and keep being
            # overlaid — their entries are for different (slot, epoch)
            # pairs.
            counts = np.asarray(pend.sub[2])[pend.pos]
            self._promote(int(counts[bad].max()), pend)

    def drain(self) -> "SkylineStream":
        """Block until any deferred slot-overflow check from previous
        feeds has settled (promoting if a front outgrew its slot). The
        explicit, sanctioned synchronization point — tests and shutdown
        call it; the serving ops (`feed`/`tick`/`snapshot`) never do."""
        self._force_resolve()
        return self

    def _promote(self, need: int, pend: _Pending) -> None:
        """Move this stream's slots to the next rows bucket that holds
        ``need`` front rows, splicing the pending wave's inserted epoch
        states in at each tenant's recorded ring slot; the old slots go
        back to their arena's free list."""
        eng = self.engine
        new_rows = slot_rows_bucket(need, eng.min_slab_rows, self.cap)
        vals = _slab_promote_fn(self.rows, max(new_rows, self.rows),
                                self.q)(
            self.arena.leaves(), self._idx(), pend.epochs,
            tuple(pend.sub), pend.pos, pend.alive)
        if new_rows <= self.rows:
            # an earlier resolve already promoted past this record's
            # need (records settle independently): splice the withheld
            # states into the slots we already hold
            self.arena.set_leaves(_slab_put_fn(self.q, self.arena.donate)(
                self.arena.leaves(), self._idx(), vals))
            return
        new_arena = eng._arena(self.d, self.dtype, self.epochs, new_rows)
        new_slots = new_arena.lease(self.q)
        new_arena.set_leaves(_slab_put_fn(self.q, new_arena.donate)(
            new_arena.leaves(), np.asarray(new_slots, np.int32), vals))
        self.arena.release(self.slots)
        self.arena, self.slots, self.rows = new_arena, new_slots, new_rows

    def feed(self, chunks: Sequence[jnp.ndarray | None], *,
             masks: Sequence[jnp.ndarray | None] | None = None,
             ) -> "SkylineStream":
        """Absorb one arriving chunk per stream (``None`` / length-0 for
        streams with no new data) in a single insert dispatch (windowed
        streams: into each tenant's current head epoch). Never waits on
        the device: an unresolved overflow check from a previous wave is
        chained straight into this wave's jitted program."""
        items, mlist = self._feed_args(chunks, masks)
        _wave_feed(self.engine, [(self, items, mlist)])
        return self

    def _feed_args(self, chunks, masks) -> tuple[list, list]:
        """Validate one feed's per-stream chunk/mask lists (shared by
        the direct `feed` path and the serve loop's wave builder)."""
        if len(chunks) != self.q:
            raise ValueError(f"got {len(chunks)} chunks for {self.q} "
                             f"streams")
        if masks is None:
            masks = [None] * self.q
        elif len(masks) != self.q:
            raise ValueError(f"got {len(masks)} masks for {self.q} "
                             f"streams")
        items = [np.zeros((0, self.d), self.dtype) if c is None else c
                 for c in chunks]
        for c in items:
            if c.shape[1:] != (self.d,):
                raise ValueError(f"chunk shape {c.shape} does not match "
                                 f"stream d={self.d}")
        return items, list(masks)

    # -- epoch ring (windowed streams) -------------------------------------

    def tick(self, tenants: Sequence[int] | None = None) -> bool:
        """Open a new head epoch — for every tenant, or only the listed
        ones — in ONE dispatch; for a tenant with a full ring, the
        claimed slot held its oldest epoch and clearing it IS the expiry
        (O(1) — nothing recomputed). Each tenant has its own ring clock,
        so deadline-aware waves can age tenants at different rates.
        Returns whether any selected tenant expired an epoch."""
        if not self.windowed:
            raise ValueError("tick() needs a windowed stream "
                             "(StreamOptions(window_epochs=E))")
        self._maybe_resolve()
        sel = self._tenant_sel(tenants)
        new_head, new_active, expired = windowed.ring_advance(
            self._head, self._active, self.epochs)
        self.arena.set_leaves(_slab_clear_epoch_fn(self.arena.donate)(
            self.arena.leaves(), self._idx(),
            new_head.astype(np.int32), sel))
        for p in self._pendings:
            # pending entries whose ring slot was just cleared die with
            # it — the cleared epoch is authoritative now
            p.alive &= ~(sel & (p.epochs == new_head))
        self._head = np.where(sel, new_head, self._head).astype(np.int32)
        self._active = np.where(sel, new_active,
                                self._active).astype(np.int32)
        self.ticks += 1
        self.engine.batches_dispatched += 1
        return bool(np.any(expired & sel))

    def expire_epoch(self,
                     tenants: Sequence[int] | None = None,
                     ) -> "SkylineStream":
        """Drop the tail epoch of the selected tenants (default: all) in
        O(1) without opening a new one (expiring the only epoch empties
        it in place)."""
        if not self.windowed:
            raise ValueError("expire_epoch() needs a windowed stream")
        self._maybe_resolve()
        sel = self._tenant_sel(tenants)
        tail = windowed.ring_tail(self._head, self._active, self.epochs)
        self.arena.set_leaves(_slab_clear_epoch_fn(self.arena.donate)(
            self.arena.leaves(), self._idx(), tail.astype(np.int32),
            sel))
        for p in self._pendings:
            p.alive &= ~(sel & (p.epochs == tail))
        self._active = np.where(sel, np.maximum(self._active - 1, 1),
                                self._active).astype(np.int32)
        self.engine.batches_dispatched += 1
        return self

    # -- reads -------------------------------------------------------------

    def snapshot(self) -> list[SkyBuffer]:
        """Canonical `SkyBuffer` per live stream (non-destructive):
        windowed streams merge their epoch ring on read, unbounded ones
        canonicalize the packed antichain. An unresolved overflow record
        from a previous feed is overlaid INSIDE the jitted program — the
        read never host-blocks on the deferred fits vector."""
        self._maybe_resolve()
        pargs = self._pend_args()
        buf = _slab_snapshot_fn(self.engine.cfg, self.rows, self.epochs,
                                len(pargs) // 4)(
            self.arena.leaves(), self._idx(), *pargs)
        return list(_unpack_fn(self.q)(buf))

    def counters(self) -> dict[str, np.ndarray]:
        """Per-stream running stats (syncs its OWN scalars to host — an
        unresolved overflow record is overlaid in-program, like
        `snapshot`). For windowed streams ``count`` is the
        *retained-candidate* total (sum of per-epoch antichain sizes) —
        the window front size needs `snapshot` (cross-epoch dominance is
        resolved on read)."""
        self._maybe_resolve()
        pargs = self._pend_args()
        count, seen, chunks, overflow, per_epoch = _slab_counters_fn(
            len(pargs) // 4)(self.arena.leaves(), self._idx(), *pargs)
        # per-epoch front sizes into the engine histogram — counters()
        # is an off-hot-path host sync already (it is NOT in the R1
        # skylint HOT_PATHS), so the recording costs nothing extra
        self.engine.record_epoch_fronts(self.d, self.epochs,
                                        np.asarray(per_epoch))
        return {"count": np.asarray(count), "seen": np.asarray(seen),
                "chunks": np.asarray(chunks),
                "overflow": np.asarray(overflow)}

    def close(self) -> None:
        """Return the leased slots to the arena free list (any deferred
        fits check dies with the stream — nothing reads it again).

        A stream that was actually fed leaves its per-epoch front sizes
        in the engine's histogram on the way out (one final `counters`
        sync — close is not a hot path), so later `open_stream` calls
        can auto-size ``epoch_capacity`` from observed workloads."""
        if self.slots and self.chunks_fed:
            self.counters()
        self._pendings = []
        if self.slots:
            self.arena.release(self.slots)
            self.slots = []


# --------------------------------------------------------------------------
# Topology calibration: measure, don't guess, the vmap/sharded threshold
# --------------------------------------------------------------------------

def _candidate_factorings(engine: SkylineEngine,
                          d: int) -> list[tuple[int, int]]:
    """Every (queries x workers) factoring of the engine mesh's device
    count whose workers axis divides cfg's partition count at
    dimensionality ``d`` (the fused program's requirement)."""
    ndev = int(engine.mesh.devices.size)
    from repro.core.parallel import effective_parts
    p, _ = effective_parts(engine.cfg, d)
    return [(ndev // wa, wa) for wa in range(1, ndev + 1)
            if ndev % wa == 0 and p % wa == 0]


def calibrate_shard_threshold(engine: SkylineEngine, *,
                              bucket_sizes: Sequence[int] = (1024, 4096,
                                                            16384),
                              q: int | None = None, d: int = 4,
                              repeat: int = 3, apply: bool = True,
                              factorings: bool = True,
                              ) -> dict[str, Any]:
    """Measure vmap vs 2-D-sharded dispatch at a few N buckets on the
    live topology and set ``engine.shard_threshold_n`` — and, with
    ``factorings=True``, the per-bucket (queries x workers) mesh
    *factoring* — from data.

    For each bucket size a synthetic batch is packed once and timed
    through the compiled vmap pipeline and every candidate factoring of
    the mesh's device count (best-of-``repeat`` after a warmup that also
    pays compilation); the sharded time of a bucket is its best
    factoring's. The calibrated threshold is the smallest measured
    bucket from which the sharded program wins at every larger measured
    bucket as well (the threshold routes all larger buckets sharded); if
    no such bucket exists (typical on a single host where XLA:CPU
    already multithreads the vmapped batch), the threshold is
    effectively infinite so the engine stays on the vmap path at every
    size. Winning factorings land in ``engine.factorings`` (bucket ->
    (qa, wa, merge-mode)), which `SkylineEngine._mesh_for` /
    `_merge_mode_for` consult on dispatch — closing the last static
    mesh choice the throughput_sharded sweep showed matters (different
    factorings win at different N), and resolving ``cfg.merge ==
    'auto'`` per bucket: the winning factoring is additionally timed
    under the tree merge, and the faster topology becomes the bucket's
    merge-mode column. Returns a report dict (``threshold_n``,
    per-bucket timings incl. every factoring and both merge modes,
    chosen factorings as ``"QxW:mode"`` strings); with ``apply=False``
    the engine is left untouched.
    """
    if engine.mesh is None:
        return {"applied": False, "threshold_n": engine.shard_threshold_n,
                "measurements": {}, "factorings": {},
                "reason": "no mesh: vmap-only engine"}
    from repro.launch.mesh import make_engine_mesh
    # grid/angular derive their partition count from d, so a factoring
    # calibrated at one d can violate `p % workers == 0` at another —
    # per-bucket factorings are only stored for the d-independent
    # strategies; the threshold itself is still calibrated
    if engine.cfg.strategy not in ("sliced", "random"):
        factorings = False
    q = q or max(engine.mesh.shape[engine.q_axis], engine.min_q_bucket)
    cands = (_candidate_factorings(engine, d) if factorings
             else [tuple(engine.mesh.shape[a]
                         for a in (engine.q_axis, engine.w_axis))])
    meshes = {f: (engine.mesh
                  if f == tuple(engine.mesh.shape[a] for a in
                                (engine.q_axis, engine.w_axis))
                  else make_engine_mesh(f[0], f[1], q_axis=engine.q_axis,
                                        w_axis=engine.w_axis))
              for f in cands}
    measurements: dict[int, dict[str, Any]] = {}
    chosen: dict[int, tuple[int, int, str]] = {}
    for size in sorted(set(bucket_sizes)):
        nb = _next_bucket(size, engine.min_n_bucket)
        if nb in measurements:
            continue
        rng = np.random.default_rng(nb)
        queries = [jnp.asarray(rng.random((nb, d)), jnp.float32)
                   for _ in range(q)]

        def measure(fn, pts_b, mask_b, keys_b):
            jax.block_until_ready(fn(pts_b, mask_b, keys_b)[0].points)
            best = float("inf")
            for _ in range(repeat):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(pts_b, mask_b, keys_b)[0].points)
                best = min(best, time.perf_counter() - t0)
            return best

        qb = _next_bucket(q, engine.min_q_bucket)
        pts_b, mask_b = engine._pack(queries, [None] * q, range(q), qb)
        keys_b = jax.random.split(jax.random.PRNGKey(0), qb)
        timings: dict[str, float] = {
            "vmap": measure(fused_skyline_batch_fn(engine.cfg),
                            pts_b, mask_b, keys_b)}
        per_fact: dict[str, float] = {}
        for fact, mesh in meshes.items():
            qa, wa = fact
            qb_f = _round_up(_next_bucket(q, max(engine.min_q_bucket,
                                                 qa)), qa)
            pts_f, mask_f = engine._pack(queries, [None] * q, range(q),
                                         qb_f)
            keys_f = jax.random.split(jax.random.PRNGKey(0), qb_f)
            per_fact[f"{qa}x{wa}"] = measure(
                fused_skyline_batch_fn(engine.cfg, mesh, engine.q_axis,
                                       engine.w_axis),
                pts_f, mask_f, keys_f)
        best_name = min(per_fact, key=per_fact.get)
        qa, wa = (int(x) for x in best_name.split("x"))
        # merge-topology column: time the tree merge on the winning
        # factoring (the flat timing is that factoring's entry above)
        # so 'auto' configs route each bucket through the measured
        # winner instead of the modeled-bytes default
        cfg_tree = dataclasses.replace(engine.cfg, merge="tree")
        qb_f = _round_up(_next_bucket(q, max(engine.min_q_bucket, qa)),
                         qa)
        pts_f, mask_f = engine._pack(queries, [None] * q, range(q), qb_f)
        keys_f = jax.random.split(jax.random.PRNGKey(0), qb_f)
        tree_t = measure(
            fused_skyline_batch_fn(cfg_tree, meshes[(qa, wa)],
                                   engine.q_axis, engine.w_axis),
            pts_f, mask_f, keys_f)
        mode = "tree" if tree_t < per_fact[best_name] else "flat"
        chosen[nb] = (qa, wa, mode)
        timings["sharded"] = min(per_fact[best_name], tree_t)
        timings["factorings"] = per_fact
        timings["best_factoring"] = best_name
        timings["merge"] = {"flat": per_fact[best_name], "tree": tree_t}
        timings["best_merge"] = mode
        measurements[nb] = timings
    # the threshold routes EVERY bucket at or above it to the sharded
    # program, so pick the smallest measured bucket from which sharded
    # wins at every larger measured bucket too; when no such bucket
    # exists the engine must stay on the vmap path for *all* sizes, not
    # just the measured ones
    sizes = sorted(measurements)
    threshold = sys.maxsize
    for i, nb in enumerate(sizes):
        if all(measurements[m]["sharded"] < measurements[m]["vmap"]
               for m in sizes[i:]):
            threshold = nb
            break
    if apply:
        engine.shard_threshold_n = threshold
        if factorings:
            engine.factorings.update(chosen)
        for nb, t in measurements.items():
            engine.wave_time_hints[(d, "float32", nb)] = min(
                t["vmap"], t["sharded"])
    return {"applied": apply, "threshold_n": threshold,
            "measurements": measurements,
            "factorings": ({nb: f"{f[0]}x{f[1]}:{f[2]}"
                            for nb, f in chosen.items()}
                           if factorings else {})}
