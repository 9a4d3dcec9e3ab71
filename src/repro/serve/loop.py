"""Async continuous-batching serve loop over `SkylineEngine`.

The engine answers synchronous calls; production skyline serving is a
request *stream* with deadlines. `ServeLoop` turns the engine into that
front-end with the dispatch-ahead shape proven by LLM serving stacks:

  intake  ->  admission  ->  coalesce  ->  pack+dispatch   (staging
                                            thread, never waits on the
                                            device)
                               device executes wave k
              completion thread observes wave k finishing while the
              staging thread is already packing wave k+1

* **Dispatch-ahead double buffering.** Up to ``depth`` waves are in
  flight: the staging thread stages (level-1 host pack) and dispatches
  wave k+1 while the device still executes wave k. Completion is
  observed by a separate thread that blocks on the wave's output
  buffers, so the staging thread never blocks on the device — host pack
  time hides behind device compute. ``depth=1`` disables the overlap
  (the A/B knob the `serving_latency` benchmark flips).

* **Cross-tenant feed coalescing.** Pending `SkylineStream.feed` work
  items whose streams lease from the same slab bucket fuse into ONE
  gather+insert+scatter dispatch per wave (`repro.serve.engine`'s
  `_wave_feed`) — bit-for-bit equal to feeding the streams serially.

* **Deadline-aware admission with load shedding.** Work items carry an
  absolute deadline (`time.monotonic` instant). The scheduler processes
  earliest-deadline-first, sheds items that the EWMA wave-time model
  says cannot meet their deadline (or *degrades* them — subsampling a
  query's data — when ``degrade=True``), and under queue overload sheds
  oldest-deadline-first until the backlog fits.

Every stream mutation happens on the staging thread, so streams need no
locks; the completion thread only blocks on device buffers and resolves
tickets. The loop never calls a blocking stream settle — overflow
promotion rides the engine's fully-async pending-record path, and
repeated overflows of the same slab slot inside one in-flight window
chain onto the live pending records wave over wave (`_wave_feed`
overlays every outstanding record in-program), so no serving code path
retains a sanctioned blocking read; `drain` remains the only explicit
settle, for shutdown and tests.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Sequence

import jax
import numpy as np

from repro.serve.api import SkylineRequest
from repro.serve.engine import (SkylineEngine, SkylineStream, _next_bucket,
                                _wave_feed)

__all__ = ["ServeLoop", "Ticket"]


class Ticket:
    """Future handed back by `ServeLoop.submit` / `ServeLoop.feed`.

    ``status`` is ``"pending"`` until the completion thread resolves it
    to ``"ok"`` (``result``/``latency`` are set; ``degraded`` marks a
    query answered on subsampled data to meet its deadline) or the
    admission controller resolves it to ``"shed"``.
    """

    __slots__ = ("kind", "request", "stream", "chunks", "masks",
                 "deadline", "submitted_at", "status", "result",
                 "latency", "degraded", "_event")

    def __init__(self, kind, *, request=None, stream=None, chunks=None,
                 masks=None, deadline=None, submitted_at=0.0):
        self.kind = kind            # "query" | "feed"
        self.request = request
        self.stream = stream
        self.chunks = chunks
        self.masks = masks
        self.deadline = deadline
        self.submitted_at = submitted_at
        self.status = "pending"
        self.result = None
        self.latency = None
        self.degraded = False
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> "Ticket":
        if not self._event.wait(timeout):
            raise TimeoutError("ticket not resolved in time")
        return self


class _Wave:
    """One in-flight dispatch: the tickets it answers, the device
    buffers whose readiness marks its completion, the wave-time model
    buckets it updates, and its clock."""

    __slots__ = ("tickets", "markers", "keys", "dispatched_at")

    def __init__(self, tickets, markers, keys, dispatched_at):
        self.tickets = tickets
        self.markers = markers
        self.keys = keys
        self.dispatched_at = dispatched_at


_STOP = object()


class ServeLoop:
    """Continuous-batching front-end: feed it `SkylineRequest`s and
    stream feeds, get `Ticket` futures back.

    ``depth`` is the dispatch-ahead window (1 = no overlap);
    ``max_wave`` caps the work items fused per wave; ``max_queue``
    bounds the backlog (beyond it, oldest-deadline-first shedding);
    ``degrade`` lets at-risk queries run on subsampled data instead of
    being shed. Use as a context manager, or call `start`/`close`.
    """

    def __init__(self, engine: SkylineEngine, *, depth: int = 2,
                 max_wave: int = 8, max_queue: int = 1024,
                 degrade: bool = False, ewma_alpha: float = 0.25,
                 clock=time.monotonic):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if max_wave < 1:
            raise ValueError(f"max_wave must be >= 1, got {max_wave}")
        self.engine = engine
        self.depth = depth
        self.max_wave = max_wave
        self.max_queue = max_queue
        self.degrade = degrade
        self._alpha = ewma_alpha
        self._clock = clock
        self._queue: collections.deque[Ticket] = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._inflight = 0
        self._stopping = False
        self._started = False
        self._done_q: collections.deque = collections.deque()
        self._done_ev = threading.Event()
        # streams with unresolved pending overflow records, polled by
        # the staging thread whenever it would otherwise sit idle
        self._watch: dict[int, SkylineStream] = {}
        # wave-time model for admission: a per-(d, dtype, rows-bucket)
        # EWMA table of dispatch->complete times, seeded from the
        # engine's calibration timings when `calibrate_shard_threshold`
        # ran (`engine.wave_time_hints`); `_ewma` is the catch-all
        # scalar for buckets with no entry yet
        self._ewma = 0.0
        self._ewma_tab: dict[tuple, float] = dict(
            getattr(engine, "wave_time_hints", {}) or {})
        # kernel-tuning sweep timings ("sweep/d=4/dtype=float32") give a
        # weak per-(d, dtype) floor for buckets calibration never saw
        self._tuning_floor: dict[tuple, float] = {}
        table = getattr(engine, "kernel_tuning", None)
        for key, entry in (getattr(table, "entries", None) or {}).items():
            parts = key.split("/")
            if parts[0] == "sweep" and len(parts) == 3:
                try:
                    d = int(parts[1].split("=")[1])
                    dt = parts[2].split("=")[1]
                except (IndexError, ValueError):
                    continue
                self._tuning_floor[(d, dt)] = entry.time_us * 1e-6
        self.stats = {"completed": 0, "shed": 0, "degraded": 0,
                      "waves": 0, "coalesced_feeds": 0}

    # -- lifecycle ---------------------------------------------------------

    def start_serving(self) -> "ServeLoop":
        if self._started:
            return self
        self._started = True
        self._stager = threading.Thread(target=self._stage_loop,
                                        name="skyline-serve-stage",
                                        daemon=True)
        self._completer = threading.Thread(target=self._complete_loop,
                                           name="skyline-serve-complete",
                                           daemon=True)
        self._stager.start()
        self._completer.start()
        return self

    def __enter__(self) -> "ServeLoop":
        return self.start_serving()

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Flush the backlog, wait for in-flight waves, stop threads."""
        if not self._started:
            return
        with self._work:
            self._stopping = True
            self._work.notify_all()
        self._stager.join()
        self._done_q.append(_STOP)
        self._done_ev.set()
        self._completer.join()
        self._started = False

    def drain(self) -> "ServeLoop":
        """Block until every accepted item has resolved (the sanctioned
        synchronization point — serving calls never wait)."""
        with self._work:
            self._work.wait_for(
                lambda: not self._queue and self._inflight == 0)
        return self

    # -- intake ------------------------------------------------------------

    def submit(self, request: SkylineRequest) -> Ticket:
        """Enqueue one skyline query; its optional ``deadline`` rides
        into admission control."""
        if not isinstance(request, SkylineRequest):
            raise TypeError("submit() takes a SkylineRequest")
        t = Ticket("query", request=request, deadline=request.deadline,
                   submitted_at=self._clock())
        self._enqueue(t)
        return t

    def feed(self, stream: SkylineStream,
             chunks: Sequence, *, masks: Sequence | None = None,
             deadline: float | None = None) -> Ticket:
        """Enqueue one stream feed; feeds for streams sharing a slab
        bucket coalesce into one wave dispatch."""
        items, mlist = stream._feed_args(chunks, masks)
        t = Ticket("feed", stream=stream, chunks=items, masks=mlist,
                   deadline=deadline, submitted_at=self._clock())
        self._enqueue(t)
        return t

    def _enqueue(self, t: Ticket) -> None:
        if not self._started:
            raise RuntimeError("serve loop is not running (use `with "
                               "ServeLoop(engine):` or call start())")
        with self._work:
            self._queue.append(t)
            self._work.notify_all()

    # -- staging thread ----------------------------------------------------

    def _stage_loop(self) -> None:
        while True:
            with self._work:
                # the dispatch-ahead gate sits BEFORE staging: with
                # depth=1 nothing is staged until the previous wave
                # fully completed (no overlap); with depth=k the host
                # stages wave k+1 while the device runs wave k. While
                # streams hold pending overflow records the wait wakes
                # on a short timeout so idle time drains them eagerly.
                self._work.wait_for(
                    lambda: (self._queue and self._inflight < self.depth)
                    or self._stopping,
                    timeout=(self._POLL_S if self._watch else None))
                if self._stopping and not self._queue:
                    return
                batch: list[Ticket] = []
                if self._queue and (self._inflight < self.depth
                                    or self._stopping):
                    batch = self._admit_locked()
                    if batch:
                        self._inflight += 1
            if not batch:
                self._poll_watched()
                continue
            wave = self._stage_once(batch)
            self._done_q.append(wave)
            self._done_ev.set()

    _POLL_S = 0.002  # idle pending-drain poll interval

    def _poll_watched(self) -> None:
        """Idle-time maintenance on the staging thread (the single
        stream mutator, so streams stay lock-free): non-blocking poll
        of every stream holding pending overflow records; each record
        is released — with the full-capacity sub-state it pins — as
        soon as the device has delivered its fits vector, instead of
        at the stream's next serving op."""
        for sid in list(self._watch):
            if not self._watch[sid].poll():
                del self._watch[sid]

    def _admit_locked(self) -> list[Ticket]:
        """Pop the next wave's work items, earliest deadline first;
        shed what the wave-time model says cannot make it (callers hold
        the lock)."""
        now = self._clock()
        if len(self._queue) > self.max_queue:
            # overload: shed oldest-deadline-first until the backlog
            # fits (items with no deadline are kept — they can wait)
            dated = sorted((t for t in self._queue
                            if t.deadline is not None),
                           key=lambda t: t.deadline)
            doomed = set()
            excess = len(self._queue) - self.max_queue
            for t in dated[:excess]:
                doomed.add(id(t))
                self._shed(t)
            self._queue = collections.deque(
                t for t in self._queue if id(t) not in doomed)
        order = sorted(self._queue,
                       key=lambda t: (t.deadline is None, t.deadline,
                                      t.submitted_at))
        batch: list[Ticket] = []
        for t in order[:self.max_wave]:
            self._queue.remove(t)
            est = now + self._wave_time(self._model_key(t)) \
                * (self._inflight + 1)
            if t.deadline is not None and est > t.deadline:
                if self.degrade and t.kind == "query" \
                        and t.request.data.shape[0] > 1:
                    # answer on every other row instead of not at all
                    t.request = dataclasses.replace(
                        t.request, data=np.asarray(t.request.data)[::2],
                        mask=(None if t.request.mask is None else
                              np.asarray(t.request.mask)[::2]))
                    t.degraded = True
                    self.stats["degraded"] += 1
                else:
                    self._shed(t)
                    continue
            batch.append(t)
        return batch

    def _shed(self, t: Ticket) -> None:
        t.status = "shed"
        self.stats["shed"] += 1
        t._event.set()

    # -- wave-time model ---------------------------------------------------

    def _model_key(self, t: Ticket) -> tuple:
        """The EWMA-table bucket of one work item: (d, dtype, rows
        bucket) — slot rows for stream feeds, the padded query-length
        bucket for queries (the same keys `engine.wave_time_hints`
        seeds)."""
        if t.kind == "feed":
            s = t.stream
            return (s.d, np.dtype(s.dtype).name, s.rows)
        data = t.request.data
        n, d = data.shape
        return (d, np.dtype(data.dtype).name,
                _next_bucket(n, self.engine.min_n_bucket))

    def _wave_time(self, key: tuple) -> float:
        """Modeled wave time for one bucket: its EWMA entry, falling
        back to the cross-bucket scalar, then to the kernel-tuning
        floor, until the bucket has history."""
        t = self._ewma_tab.get(key)
        if t is not None:
            return t
        if self._ewma:
            return self._ewma
        return self._tuning_floor.get(key[:2], 0.0)

    def _stage_once(self, batch: list[Ticket]) -> _Wave:
        """Pack and dispatch one wave WITHOUT waiting on the device:
        queries go through `SkylineEngine.submit_many` (one bucketed
        dispatch per group), same-bucket stream feeds fuse via
        `_wave_feed`. Returns the in-flight record whose markers the
        completion thread blocks on."""
        markers: list = []
        queries = [t for t in batch if t.kind == "query"]
        feeds = [t for t in batch if t.kind == "feed"]
        if queries:
            results = self.engine.submit_many(
                [t.request for t in queries])
            for t, (buf, st) in zip(queries, results):
                t.result = (buf, st)
                markers.append(buf.points)
        if feeds:
            waves: dict[tuple, list] = {}
            for t in feeds:
                s = t.stream
                s._maybe_resolve()  # promotions change the bucket key
                waves.setdefault((id(s.arena), s.rows, s.cap),
                                 []).append(t)
            for group in waves.values():
                parts = [(t.stream, t.chunks, t.masks) for t in group]
                wstats = _wave_feed(self.engine, parts)
                self.stats["coalesced_feeds"] += len(group) - 1
                # a stats leaf of the wave program: small, ready exactly
                # when the wave's arena update is, and — unlike the
                # arena leaves, which the NEXT wave consumes (buffer
                # donation) — never invalidated while in flight
                markers.append(wstats[sorted(wstats)[0]])
                for t in group:
                    t.result = t.stream.last_stats
                    if t.stream._pendings:
                        self._watch[id(t.stream)] = t.stream
        self.stats["waves"] += 1
        keys = sorted({self._model_key(t) for t in batch})
        return _Wave(batch, markers, keys, self._clock())

    # -- completion thread -------------------------------------------------

    def _complete_loop(self) -> None:
        while True:
            while not self._done_q:
                self._done_ev.wait()
                self._done_ev.clear()
            wave = self._done_q.popleft()
            if wave is _STOP:
                return
            for m in wave.markers:
                jax.block_until_ready(m)
            done_at = self._clock()
            wave_time = done_at - wave.dispatched_at
            for t in wave.tickets:
                t.status = "ok"
                t.latency = done_at - t.submitted_at
                self.stats["completed"] += 1
                t._event.set()
            with self._work:
                self._ewma = (wave_time if self._ewma == 0.0 else
                              self._alpha * wave_time
                              + (1 - self._alpha) * self._ewma)
                for k in wave.keys:
                    prev = self._ewma_tab.get(k)
                    self._ewma_tab[k] = (
                        wave_time if prev is None else
                        self._alpha * wave_time
                        + (1 - self._alpha) * prev)
                self._inflight -= 1
                self._work.notify_all()
