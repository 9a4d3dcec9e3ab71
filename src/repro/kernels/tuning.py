"""Persistent per-topology kernel autotuner.

The calibration machinery measures, rather than guesses, the dispatch
policy of the live topology (`repro.serve.engine.calibrate_shard_threshold`
times vmap vs every mesh factoring).  This module extends it to the
kernel *geometry*: :func:`calibrate_kernels` times candidate
``(block, wtile)`` pairs per ``(family, d, dtype)`` on the runtime the
``'auto'`` impl actually resolves to, verifies every candidate bit-for-bit
against the per-pair reference, and persists the winners as a
:class:`TuningTable` — a JSON artifact CI uploads and prod loads, so both
run the same tuned geometry:

    table = calibrate_kernels(engine)          # applies to the engine
    table.save("results/kernel_tuning.json")
    ...
    REPRO_KERNEL_TUNING=results/kernel_tuning.json python serve.py

Resolution order when the engine answers an ``impl='auto'`` request:
its own calibrated table (``engine.kernel_tuning``, set by
``calibrate_kernels(engine)``), else the process default
(:func:`set_default_table`, lazily loaded from the
``REPRO_KERNEL_TUNING`` env var — `repro.launch.env` plumbs it).  A
config that pins ``wtile`` explicitly, or any non-'auto' ``impl``, is
never overridden: the table tunes only what the user left to 'auto'.

Every tuned geometry is pure schedule — the sweep contract guarantees
any (block, wtile) is bit-identical to any other — so applying a table
can change performance and buffer padding, never membership decisions.
Candidates that fail the bitwise check (a broken backend, a miscompile)
are excluded from winning and reported with ``bitwise_ok=False``; CI
fails on any such entry (benchmarks/run.py ``kernel_autotune``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TuneEntry", "TuningTable", "calibrate_kernels",
           "check_platform", "default_table", "set_default_table",
           "tuning_key"]

ENV_VAR = "REPRO_KERNEL_TUNING"


def tuning_key(family: str, d: int, dtype) -> str:
    """Canonical table key: ``family/d=D/dtype=NAME``."""
    return f"{family}/d={int(d)}/dtype={jnp.dtype(dtype).name}"


@dataclasses.dataclass(frozen=True)
class TuneEntry:
    """One winning kernel geometry for a (family, d, dtype) key."""
    block: int
    wtile: int
    time_us: float
    impl: str                 # the impl string the timing ran under
    bitwise_ok: bool = True   # vs the per-pair / full-matrix reference


@dataclasses.dataclass
class TuningTable:
    """Tuned (block, wtile) per ``family/d=D/dtype=NAME`` key, plus the
    topology it was measured on.  Any geometry is bit-identical, but a
    table is applied only on the platform that timed it
    (`check_platform`)."""
    entries: dict[str, TuneEntry] = dataclasses.field(default_factory=dict)
    topology: dict[str, Any] = dataclasses.field(default_factory=dict)

    def lookup(self, family: str, d: int, dtype) -> TuneEntry | None:
        return self.entries.get(tuning_key(family, d, dtype))

    def __len__(self) -> int:
        return len(self.entries)

    def to_json(self) -> dict:
        return {"version": 1, "topology": self.topology,
                "entries": {k: dataclasses.asdict(e)
                            for k, e in self.entries.items()}}

    @classmethod
    def from_json(cls, doc: dict) -> "TuningTable":
        entries = {k: TuneEntry(**{f: v[f] for f in
                                   ("block", "wtile", "time_us", "impl",
                                    "bitwise_ok") if f in v})
                   for k, v in doc.get("entries", {}).items()}
        return cls(entries=entries, topology=doc.get("topology", {}))

    def save(self, path: str) -> str:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


# -- process-default table (env-loadable) ----------------------------------

_DEFAULT: TuningTable | None = None
_DEFAULT_LOADED = False


def check_platform(table: TuningTable, source: str = "table") -> None:
    """Refuse a table timed on another backend: a geometry measured on
    the CPU says nothing about the TPU (and vice versa), so it must
    never be applied there.  A table with no recorded platform cannot
    show where it was measured and is refused too."""
    want = jax.default_backend()
    got = table.topology.get("platform")
    if got != want:
        raise ValueError(
            f"kernel tuning {source} was measured on platform {got!r}, "
            f"not the running {want!r}; recalibrate on this platform "
            f"(repro.kernels.tuning.calibrate_kernels)")


def set_default_table(table: TuningTable | None) -> None:
    """Install ``table`` as the process default (None clears it and
    re-arms the env-var load)."""
    global _DEFAULT, _DEFAULT_LOADED
    if table is not None:
        check_platform(table)
    _DEFAULT = table
    _DEFAULT_LOADED = table is not None


def default_table() -> TuningTable | None:
    """The process-default tuning table: whatever `set_default_table`
    installed, else a one-time lazy load from ``$REPRO_KERNEL_TUNING``.
    A named table that cannot be read, or that was measured on another
    platform, raises: a run that asked for a tuned geometry must not
    silently run another one."""
    global _DEFAULT, _DEFAULT_LOADED
    if not _DEFAULT_LOADED:
        path = os.environ.get(ENV_VAR)
        if path:
            try:
                table = TuningTable.load(path)
            except (OSError, ValueError, KeyError, TypeError) as e:
                raise ValueError(f"${ENV_VAR}={path!r} cannot be read as "
                                 f"a tuning table: {e}") from e
            check_platform(table, repr(path))
            _DEFAULT = table
        _DEFAULT_LOADED = True
    return _DEFAULT


# -- calibration -----------------------------------------------------------

def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _bitwise_equal(a, b) -> bool:
    """Bit-level equality for float buffers (NaN-proof, -0.0-strict)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    return bool(np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def _interleaved_best(cands: dict[str, Any], repeat: int) -> dict[str, float]:
    """Best-of-``repeat`` wall time per candidate thunk, rounds
    interleaved (and order alternated) so clock drift and turbo decay
    hit every candidate equally — the `local_phase` benchmark idiom."""
    for fn in cands.values():     # warmup pays compilation
        jax.block_until_ready(fn())
    best = {k: float("inf") for k in cands}
    for r in range(repeat):
        order = list(cands) if r % 2 == 0 else list(reversed(cands))
        for k in order:
            t0 = time.perf_counter()
            jax.block_until_ready(cands[k]())
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def _sweep_candidates(blocks: Sequence[int], capacity: int,
                      ) -> list[tuple[int, int]]:
    """(block, wtile) grid: untiled, one-block and two-block tiles per
    block size, filtered to divisors of that block's window."""
    out = []
    for b in blocks:
        wcap = _ceil_to(capacity, b)
        for t in (0, b, 2 * b):
            if t > wcap or (t and wcap % t):
                continue
            out.append((b, t))
    return out


def calibrate_kernels(engine=None, *,
                      ds: Sequence[int] = (4,),
                      dtypes: Sequence[Any] = (jnp.float32,),
                      n: int = 16_384, p: int = 8,
                      capacity: int | None = None,
                      blocks: Sequence[int] = (128, 256, 512),
                      repeat: int = 3, apply: bool = True,
                      verify: bool = True,
                      path: str | None = None) -> dict[str, Any]:
    """Time candidate kernel geometries on the live topology and build
    the winning :class:`TuningTable`.

    For every ``(d, dtype)``: the *sweep* family times each candidate
    ``(block, wtile)`` through `local_skyline_batch` on a synthetic
    ``(p, n/p, d)`` partition batch (interleaved best-of-``repeat``),
    and the *dominance* family times each block size through
    `dominated_mask`.  With ``verify=True`` (the default) every sweep
    candidate is checked bit-for-bit against the per-pair reference and
    every dominance candidate against the full-matrix reference before
    it may win; divergent candidates are recorded with
    ``bitwise_ok=False`` and never selected.

    ``engine`` supplies the config whose 'auto' resolution the table
    will serve (capacity, impl) and — with ``apply=True`` — receives the
    table as ``engine.kernel_tuning``; ``engine=None`` calibrates the
    process default config instead and installs the table with
    `set_default_table`.  ``path`` additionally persists the JSON
    artifact.  Returns a report dict (``table``, per-key candidate
    timings, ``divergent`` keys).
    """
    from repro.core.parallel import SkyConfig
    from repro.core.sfs import local_skyline_batch
    from repro.kernels.backend import impl_max_d, resolve_spec
    from repro.kernels.dominance import dominated_mask
    from repro.kernels.dominance.ref import dominated_mask_ref

    cfg = engine.cfg if engine is not None else SkyConfig()
    capacity = int(capacity or cfg.capacity)
    spec = resolve_spec(cfg.impl)
    table = TuningTable(topology={
        "platform": jax.default_backend(),
        "devices": len(jax.devices()),
        "impl": spec.name, "n": int(n), "p": int(p),
        "capacity": capacity})
    report: dict[str, Any] = {"impl": spec.name, "keys": {},
                              "divergent": []}

    psz = _ceil_to(max(n // max(p, 1), 1), max(blocks))
    for d in ds:
        if spec.max_d is not None and d > spec.max_d:
            continue
        for dtype in dtypes:
            rng = np.random.default_rng(d * 1000 + 17)
            # quantized coordinates: dense dominance ties, the
            # regime where the window test does real work
            pts = jnp.asarray(
                np.round(rng.random((p, psz, d)) * 64) / 64, dtype)
            mask = jnp.ones((p, psz), jnp.bool_)

            # --- sweep family: (block, wtile) candidates --------------
            cands = _sweep_candidates(blocks, capacity)
            thunks = {
                f"b{b}/t{t}": (lambda b=b, t=t: local_skyline_batch(
                    pts, mask, capacity=capacity, block=b,
                    impl=cfg.impl, wtile=t).points)
                for (b, t) in cands}
            times = _interleaved_best(thunks, repeat)
            ok: dict[str, bool] = {}
            if verify:
                for (b, t) in cands:
                    got = local_skyline_batch(pts, mask,
                                              capacity=capacity, block=b,
                                              impl=cfg.impl, wtile=t)
                    ref = local_skyline_batch(pts, mask,
                                              capacity=capacity, block=b,
                                              impl="perpair")
                    ok[f"b{b}/t{t}"] = (
                        _bitwise_equal(got.points, ref.points)
                        and _bitwise_equal(got.mask, ref.mask)
                        and _bitwise_equal(got.count, ref.count))
            else:
                ok = {k: True for k in thunks}
            key = tuning_key("sweep", d, dtype)
            report["keys"][key] = {
                "times_us": {k: round(v * 1e6, 2)
                             for k, v in times.items()},
                "bitwise_ok": ok}
            valid = [k for k in times if ok[k]]
            if not valid:
                report["divergent"].append(key)
            else:
                win = min(valid, key=times.get)
                wb, wt = (int(x[1:]) for x in win.split("/"))
                table.entries[key] = TuneEntry(
                    block=wb, wtile=wt,
                    time_us=round(times[win] * 1e6, 2),
                    impl=spec.name,
                    bitwise_ok=all(ok[k] for k in valid))
                if any(not v for v in ok.values()):
                    report["divergent"].append(key)

            # --- dominance family: block candidates -------------------
            if impl_max_d(spec.dominance) is not None \
                    and d > impl_max_d(spec.dominance):
                continue
            # one partition's worth is representative and keeps the
            # O(n^2) dominance timing off the critical calibration path
            flat = pts[0]
            fm = mask[0]
            dthunks = {
                f"b{b}": (lambda b=b: dominated_mask(
                    flat, flat, fm, impl=spec.dominance,
                    block_c=b, block_r=b))
                for b in blocks}
            dtimes = _interleaved_best(dthunks, repeat)
            dok: dict[str, bool] = {}
            if verify:
                dref = dominated_mask_ref(flat, flat, fm)
                for b in blocks:
                    got = dominated_mask(flat, flat, fm,
                                         impl=spec.dominance,
                                         block_c=b, block_r=b)
                    dok[f"b{b}"] = _bitwise_equal(got, dref)
            else:
                dok = {k: True for k in dthunks}
            dkey = tuning_key("dominance", d, dtype)
            report["keys"][dkey] = {
                "times_us": {k: round(v * 1e6, 2)
                             for k, v in dtimes.items()},
                "bitwise_ok": dok}
            dvalid = [k for k in dtimes if dok[k]]
            if not dvalid:
                report["divergent"].append(dkey)
            else:
                dwin = min(dvalid, key=dtimes.get)
                table.entries[dkey] = TuneEntry(
                    block=int(dwin[1:]), wtile=0,
                    time_us=round(dtimes[dwin] * 1e6, 2),
                    impl=spec.dominance,
                    bitwise_ok=all(dok[k] for k in dvalid))
                if any(not v for v in dok.values()):
                    report["divergent"].append(dkey)

    if apply:
        if engine is not None:
            engine.kernel_tuning = table
        else:
            set_default_table(table)
    if path:
        table.save(path)
        report["path"] = path
    report["table"] = table
    report["applied"] = apply
    return report
