"""Smoke run of the skyline engine on a TPU, through its public entry points.

    python chip_smoke.py [--seed S]      # one chip: every phase below
    python chip_smoke.py --chips 4       # four chips: the sharded path only

One chip, at the source paper's scale (data from the seeded
Borzsonyi-Kossmann-Stocker generator, `repro.core.datagen`):

  oneshot_hou   `parallel_skyline`, 2,049,280 x 7 independent (the HOU
                shape), sliced, p=8, representative filtering
  oneshot_anti  `parallel_skyline`, 1,048,576 x 3 anti-correlated
  engine_batch  `SkylineEngine.submit_many`, 32 requests of 65,536 x 4
  window        `open_stream` with a 4-epoch window, 8 chunks of
                65,536 x 4, `tick` after each, `snapshot` after the
                last two ticks
  serve         `ServeLoop`, 64 requests of 4,096 x 4, no deadlines

Four chips (`--chips 4`): the HOU-shape one-shot on a 4-worker mesh
with the flat and the tree merge, and one engine batch on the 2-D
(queries, workers) sharded program.

Every answer is checked against a plain reference written here (no
kernel or pipeline code of the repo): a row belongs to the skyline iff
no returned member dominates it, and the returned members must equal
those rows exactly, as a multiset.  That equality holds only for the
true skyline.  Any mismatch, overflow flag, shed request, missing
Pallas kernel in the compiled one-shot program, or non-TPU device
fails the run.  Times are smoke timings of one run each, cold (with
compilation) and warm, not benchmark numbers.  The last line of
standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# phase sizes (rows, attributes, requests)
HOU_N, HOU_D = 2_049_280, 7
ANTI_N, ANTI_D = 1_048_576, 3
BATCH_Q, BATCH_N = 32, 65_536
WINDOW_N = 65_536
SERVE_Q, SERVE_N = 64, 4_096


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# --------------------------------------------------------------------------
# plain reference (jnp + numpy only)
# --------------------------------------------------------------------------

def _undominated(data, members, *, block: int = 1024):
    """(N,) bool on device: rows of ``data`` that no row of ``members``
    dominates (<= in every attribute, < in one; smaller is better).
    ``members`` is padded with +inf rows, which dominate nothing, to a
    power-of-two row count so few shapes compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = 128
    while rows < len(members):
        rows *= 2
    pad = np.full((rows, data.shape[1]), np.inf, np.float32)
    pad[:len(members)] = members
    return _undominated_fn(block)(data, jnp.asarray(pad))


_UNDOMINATED = {}


def _undominated_fn(block: int):
    import jax
    import jax.numpy as jnp

    if block in _UNDOMINATED:
        return _UNDOMINATED[block]

    def run(data, s):
        n, d = data.shape
        npad = -(-n // block) * block
        x = jnp.pad(data, ((0, npad - n), (0, 0))).reshape(-1, block, d)

        def one(xb):
            le = jnp.ones((block, s.shape[0]), bool)
            lt = jnp.zeros((block, s.shape[0]), bool)
            for k in range(d):
                a, b = s[None, :, k], xb[:, k, None]
                le = le & (a <= b)
                lt = lt | (a < b)
            return ~jnp.any(le & lt, axis=1)

        return jax.lax.map(one, x).reshape(-1)[:n]

    _UNDOMINATED[block] = jax.jit(run)
    return _UNDOMINATED[block]


def _sorted_rows(rows):
    import numpy as np
    rows = np.ascontiguousarray(rows)
    return rows[np.lexsort(rows.T[::-1])] if len(rows) else rows


def check_answer(name: str, data, buf, stats=None) -> int:
    """Hold one returned SkyBuffer to the reference; returns its size."""
    import numpy as np

    flags = {"overflow": bool(buf.overflow)}
    for key in ("bucket_overflow", "local_overflow"):
        if stats is not None and key in stats:
            flags[key] = bool(np.any(np.asarray(stats[key])))
    if any(flags.values()):
        _fail(f"{name}: overflow flag raised {flags}")
    got = np.asarray(buf.points)[np.asarray(buf.mask)]
    if len(got) != int(buf.count):
        _fail(f"{name}: {len(got)} valid rows but count {int(buf.count)}")
    keep = np.asarray(_undominated(data, got))
    want = np.asarray(data)[keep]
    if not np.array_equal(_sorted_rows(got).view(np.uint32),
                          _sorted_rows(want).view(np.uint32)):
        _fail(f"{name}: skyline mismatch: {len(got)} returned, "
              f"{len(want)} in the reference")
    return len(got)


# --------------------------------------------------------------------------
# instrumentation
# --------------------------------------------------------------------------

def pallas_kernels(compiled_text: str) -> list[str]:
    """Names of the Pallas kernels (TPU custom calls) in compiled HLO."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([A-Za-z_]+)", line)
            names.add(m.group(1) if m else "?")
    return sorted(names)


class Phase:
    """Counters and geometry around one phase (cold run, then warm)."""

    def __init__(self, name: str):
        print(f"chip_smoke: phase {name} starts", file=sys.stderr,
              flush=True)
        from repro.core.parallel import trace_count
        from repro.kernels.sfs.ops import traced_geometries
        from repro.serve.engine import pack_trace_count
        self.name = name
        self._tc = lambda: {k: trace_count(k) for k in ("fused",
                                                         "fused_batch")}
        self._pc = pack_trace_count
        self._geo = traced_geometries
        self.t0 = (self._tc(), self._pc(), len(self._geo()))
        self.t1 = None

    def freeze(self):
        """Take the phase's counters now (work after this is not the
        phase's own, e.g. an extra trace to read the compiled HLO)."""
        self.t1 = (self._tc(), self._pc(), len(self._geo()))

    def timed(self, fn):
        import jax
        t = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return out, time.perf_counter() - t

    def report(self, *, cold_s, warm_s, sizes, spec, **extra):
        tc, pc, ng = self.t0
        now, pnow, gnow = self.t1 or (self._tc(), self._pc(),
                                      len(self._geo()))
        geo = self._geo()[ng:gnow]
        _say(phase=self.name, match=True, overflow=False,
             smoke_timing_cold_s=cold_s, smoke_timing_warm_s=warm_s,
             trace_count_delta={k: now[k] - tc[k] for k in now},
             pack_trace_count_delta=pnow - pc,
             skyline_sizes=sizes, kernel_spec=spec,
             sweep_geometry=[{k: g[k] for k in ("p", "n", "d", "block",
                                                "wcap", "wtile",
                                                "vmem_limit")}
                             for g in geo if not g["interpret"]],
             **extra)


# --------------------------------------------------------------------------
# one-chip phases
# --------------------------------------------------------------------------

def _key(seed: int, tag: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed), tag)


def oneshot(name, dist, n, d, cfg, seed, tag, need_kernels):
    import jax
    import jax.numpy as jnp
    from repro.core.datagen import generate
    from repro.core.parallel import fused_skyline_fn, parallel_skyline
    from repro.kernels.backend import resolve_spec

    ph = Phase(name)
    pts = jax.block_until_ready(generate(dist, _key(seed, tag), n, d))
    key = jax.random.PRNGKey(seed)
    run = lambda: parallel_skyline(pts, cfg=cfg, key=key)
    (buf, stats), cold = ph.timed(run)
    (buf2, _), warm = ph.timed(run)
    ph.freeze()
    t = time.perf_counter()
    text = fused_skyline_fn(cfg).lower(
        pts, jnp.ones((n,), bool), key).compile().as_text()
    hlo_s = time.perf_counter() - t
    kernels = pallas_kernels(text)
    if not set(need_kernels) <= set(kernels):
        _fail(f"{name}: compiled program holds Pallas kernels {kernels}, "
              f"needs {sorted(need_kernels)}")
    size = check_answer(name, pts, buf, stats)
    if int(buf2.count) != size:
        _fail(f"{name}: warm run differs from cold run")
    ph.report(cold_s=cold, warm_s=warm, sizes=[size],
              spec=resolve_spec(cfg.impl), n=n, d=d, distribution=dist,
              local_sizes=[int(v) for v in stats["local_sizes"]],
              tpu_custom_call=kernels, hlo_fetch_s=hlo_s)


def engine_batch(engine, seed):
    import jax
    from repro.core.datagen import generate
    from repro.serve.api import SkylineRequest

    ph = Phase("engine_batch")
    data = [generate("uniform", _key(seed, 100 + i), BATCH_N, 4)
            for i in range(BATCH_Q)]
    jax.block_until_ready(data)
    reqs = [SkylineRequest(data=x) for x in data]
    res, cold = ph.timed(lambda: engine.submit_many(reqs))
    res2, warm = ph.timed(lambda: engine.submit_many(reqs))
    sizes = [check_answer(f"engine_batch[{i}]", x, buf, st)
             for i, (x, (buf, st)) in enumerate(zip(data, res))]
    if [int(b.count) for b, _ in res2] != sizes:
        _fail("engine_batch: warm run differs from cold run")
    ph.report(cold_s=cold, warm_s=warm, sizes=sizes,
              spec=engine.kernel_spec, requests=len(reqs),
              dispatches=engine.batches_dispatched)


def window(engine, seed):
    import jax
    import jax.numpy as jnp
    from repro.core.datagen import generate
    from repro.serve.api import StreamOptions

    epochs, nchunk = 4, 8
    ph = Phase("window")

    def one_pass(tag):
        chunks = [generate("uniform", _key(seed, tag + i), WINDOW_N, 4)
                  for i in range(nchunk)]
        jax.block_until_ready(chunks)
        stream = engine.open_stream(4, StreamOptions(window_epochs=epochs))
        snaps = []
        t = time.perf_counter()
        for i, c in enumerate(chunks):
            stream.feed([c])
            stream.tick()
            if i >= nchunk - 2:
                snaps.append((i, stream.snapshot()[0]))
        jax.block_until_ready([s for _, s in snaps])
        dt = time.perf_counter() - t
        over = bool(stream.counters()["overflow"].any())
        stream.close()
        # after the tick that follows chunk i, the ring holds a fresh
        # empty head and the epochs of chunks i-E+2 .. i
        sizes = [check_answer(
            f"window[tag={tag}, after chunk {i}]",
            jnp.concatenate(chunks[i - epochs + 2:i + 1]), buf)
            for i, buf in snaps]
        if over:
            _fail("window: stream counters report overflow")
        return sizes, dt

    sizes, cold = one_pass(200)
    sizes2, warm = one_pass(300)
    ph.report(cold_s=cold, warm_s=warm, sizes=sizes + sizes2,
              spec=engine.kernel_spec, epochs=epochs, chunks=nchunk)


def serve(engine, seed):
    import jax
    from repro.core.datagen import generate
    from repro.serve.api import SkylineRequest
    from repro.serve.loop import ServeLoop

    ph = Phase("serve")

    def one_pass(tag):
        data = [generate("anticorrelated", _key(seed, tag + i), SERVE_N, 4)
                for i in range(SERVE_Q)]
        jax.block_until_ready(data)
        t = time.perf_counter()
        with ServeLoop(engine) as loop:
            tickets = [loop.submit(SkylineRequest(data=x)) for x in data]
            for tk in tickets:
                tk.wait(timeout=300)
            stats = dict(loop.stats)
        dt = time.perf_counter() - t
        if stats["shed"] or any(tk.status != "ok" for tk in tickets):
            _fail(f"serve: {stats['shed']} shed, statuses "
                  f"{sorted({tk.status for tk in tickets})}")
        sizes = [check_answer(f"serve[{i}]", x, *tk.result)
                 for i, (x, tk) in enumerate(zip(data, tickets))]
        return sizes, dt, stats

    sizes, cold, _ = one_pass(400)
    sizes2, warm, stats = one_pass(500)
    ph.report(cold_s=cold, warm_s=warm, sizes=sizes,
              spec=engine.kernel_spec, requests=len(sizes),
              shed=stats["shed"], waves=stats["waves"])


# --------------------------------------------------------------------------
# four-chip phase
# --------------------------------------------------------------------------

def sharded(cfg, seed):
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.datagen import generate
    from repro.core.parallel import parallel_skyline
    from repro.kernels.backend import resolve_spec
    from repro.launch.mesh import (engine_mesh_shape, make_engine_mesh,
                                   make_worker_mesh)
    from repro.serve.api import SkylineRequest
    from repro.serve.engine import SkylineEngine

    mesh = make_worker_mesh(4)
    pts = jax.device_put(generate("uniform", _key(seed, 1), HOU_N, HOU_D),
                         NamedSharding(mesh, PartitionSpec("workers")))
    jax.block_until_ready(pts)
    devs = {s.device for s in pts.addressable_shards}
    if len(devs) != 4:
        _fail(f"sharded input spans {len(devs)} device(s), not 4")
    out = {}
    for merge in ("flat", "tree"):
        ph = Phase(f"sharded_oneshot_{merge}")
        mcfg = dataclasses.replace(cfg, merge=merge)
        run = lambda: parallel_skyline(pts, cfg=mcfg, mesh=mesh,
                                       key=jax.random.PRNGKey(seed))
        (buf, stats), cold = ph.timed(run)
        _, warm = ph.timed(run)
        size = check_answer(ph.name, pts, buf, stats)
        out[merge] = buf
        ph.report(cold_s=cold, warm_s=warm, sizes=[size],
                  spec=resolve_spec(mcfg.impl), merge=merge, workers=4,
                  input_devices=sorted(str(d) for d in devs))
    for a, b in zip(out["flat"], out["tree"]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            _fail("flat and tree merge disagree")
    _say(phase="flat_vs_tree", bitwise_equal=True)

    ph = Phase("sharded_engine_batch")
    qa, wa = engine_mesh_shape(8, 4)
    engine = SkylineEngine(dataclasses.replace(cfg, capacity=4096,
                                               local_capacity=0),
                           mesh=make_engine_mesh(qa, wa),
                           shard_threshold_n=BATCH_N)
    data = [generate("uniform", _key(seed, 600 + i), BATCH_N, 4)
            for i in range(8)]
    jax.block_until_ready(data)
    reqs = [SkylineRequest(data=x) for x in data]
    res, cold = ph.timed(lambda: engine.submit_many(reqs))
    _, warm = ph.timed(lambda: engine.submit_many(reqs))
    if engine.sharded_dispatched != 2:
        _fail(f"engine batch took the sharded program "
              f"{engine.sharded_dispatched} of 2 times")
    sizes = [check_answer(f"sharded_engine_batch[{i}]", x, buf, st)
             for i, (x, (buf, st)) in enumerate(zip(data, res))]
    ph.report(cold_s=cold, warm_s=warm, sizes=sizes,
              spec=engine.kernel_spec, mesh=[qa, wa])


# --------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        _fail(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}")

    from repro.core.parallel import SkyConfig
    from repro.kernels.backend import resolve_spec
    from repro.launch.env import use_compile_cache
    from repro.serve.engine import SkylineEngine

    spec = resolve_spec("auto")
    if (spec.sweep, spec.dominance) != ("pallas", "pallas"):
        _fail(f"impl='auto' resolved to {spec} on TPU, not Pallas")
    _say(device_kind=devices[0].device_kind, device_count=len(devices),
         jax=jax.__version__, compile_cache=use_compile_cache(),
         auto_spec=spec, seed=args.seed)

    # HOU: ~1.3e4 skyline rows, ~7e3 per sliced partition (expected
    # maxima of n independent points in 7-d); the merge window must hold
    # the union of the local skylines (~5.5e4)
    hou = SkyConfig(strategy="sliced", p=8, local_capacity=16_384,
                    capacity=131_072, rep_filter="sorted")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded(hou, args.seed)
    else:
        oneshot("oneshot_hou", "uniform", HOU_N, HOU_D, hou, args.seed, 1,
                need_kernels=("sfs_sweep", "dominated_mask"))
        anti = SkyConfig(strategy="sliced", p=8, local_capacity=4_096,
                         capacity=16_384)
        oneshot("oneshot_anti", "anticorrelated", ANTI_N, ANTI_D, anti,
                args.seed, 2, need_kernels=("sfs_sweep",))
        engine = SkylineEngine(SkyConfig(strategy="sliced", p=8))
        engine_batch(engine, args.seed)
        window(engine, args.seed)
        serve(engine, args.seed)
    _say(total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
