"""Device time of each stage of the skyline pipeline, from a traced window.

    python bench/stages.py --workload <cell> --seed <n> --seconds <s>

The program runs each stage of its pipeline under a `jax.named_scope`
whose name starts with ``sky.`` (``sky.partition``, ``sky.rep_filter``,
``sky.local``, ``sky.merge``; `repro.core.parallel.STAGE_SCOPES`), and the
host work of each one-shot call under the profiler span ``sky.dispatch``.
This module extends `bench.trace`'s reduced form so that it can read
them, and leaves that form as it is:

* `load` adds the key ``"paths"``: per device, the HLO ``op_name`` path of
  each event, in the order of its ``"events"`` (``""`` where an op has
  none).  It also keeps the host spans named ``sky.*`` beside the
  ``bench.*`` ones, so that `bench.trace.idle_by_host_span` puts an idle
  gap inside the program's own dispatch down to ``sky.dispatch``.
* `scope_seconds` - per device, the union of the in-window intervals of
  the ops whose first ``sky.*`` path component is the scope, optionally
  only those whose names start with given prefixes; summed over devices.
  The union counts a ``while`` and the body ops it spans once.  Scope
  ``None`` is the busy time that no scoped op covers.
* `span_seconds` - durations of the host spans of one name that start in
  the window.

The TPU profiler names each op event by its HLO text without metadata,
and its stats carry only times, so an op's path comes from the compiled
HLO text of the program, by instruction name; the HLO text also gives
ops the compiler made without a stage the stage they come from.

The command runs one window of the cell's system under its traffic with
the profiler off, then one with it on, and prints one JSON line: per
stage, device ms per completed query summed over chips, its collectives,
and its largest op families; the unscoped ops; the busy time they add up
to; the mean and median ``sky.dispatch`` span; idle gaps by host span;
the work counters of the traced window's answers; and both windows'
queries and time per query (the cost of tracing).  Executables read from JAX's
persistent compile cache carry the op metadata of the program that
compiled them first, unless the metadata is part of the cache key, so
this command puts it there (`cache_keeps_metadata`).
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import re
import statistics
import sys
import tempfile

import numpy as np

# the program's names (`repro.core.parallel.STAGE_SCOPES`, `DISPATCH_SPAN`),
# spelled out here so that this runs on a program that has none of them
SCOPE_PREFIX = "sky."
DISPATCH_SPAN = "sky.dispatch"
COUNTERS = ("n_valid", "rep_filter_dropped", "union_size")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INST = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_RUNS = re.compile(r"\b(?:condition|body|branch_computations|true_computation|"
                   r"false_computation)=(?:\{([^}]*)\}|%?([\w.\-]+))")


def scope_of(path: str) -> str | None:
    """The first ``sky.*`` component of an op_name path, if any."""
    for part in path.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name -> op_name of a compiled HLO module's text.  An
    op the compiler made with no stage in its metadata (none at all, or
    a name the partitioner made up) takes, in this order: its fused
    root's op_name (a fusion), that of the first of its operands that
    names a stage (a rewrite computes a piece of one traced op from that
    op's inputs), or, inside a loop's body or condition or a branch, that
    of the instruction that runs it."""
    own: dict[str, str] = {}
    fused: dict[str, str] = {}       # fusion -> its fused computation
    runner: dict[str, str] = {}      # computation -> instruction running it
    comp_of: dict[str, str] = {}
    operands: dict[str, list[str]] = {}
    root: dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            comp = head.group(1)
            continue
        inst = _INST.match(line)
        if comp is None or not inst:
            continue
        is_root, name, rest = inst.groups()
        comp_of[name] = comp
        operands[name] = _OPERAND.findall(rest)
        if is_root:
            root[comp] = name
        m = _OP_NAME.search(rest)
        if m:
            own[name] = m.group(1)
        if (c := _CALLS.search(rest)):
            fused[name] = c.group(1)
        for braced, single in _RUNS.findall(rest):
            for callee in (braced or single).split(","):
                runner[callee.strip().lstrip("%")] = name

    done: dict[str, str] = {}

    def resolve(name: str, seen: frozenset = frozenset()) -> str:
        """The op_name of ``name`` if it names a stage, else the first
        inherited one that does, else its own (maybe empty)."""
        mine = own.get(name, "")
        if scope_of(mine) or name in seen:
            return mine
        if name in done:
            return done[name]
        seen = seen | {name}
        inherit = []
        if name in fused and fused[name] in root:
            inherit.append(root[fused[name]])
        inherit += [x for x in operands.get(name, ())
                    if comp_of.get(x) == comp_of[name]]
        if comp_of.get(name) in runner:
            inherit.append(runner[comp_of[name]])
        done[name] = mine
        for x in inherit:
            path = resolve(x, seen)
            if scope_of(path):
                done[name] = path
                break
        return done[name]

    return {name: p for name in comp_of if (p := resolve(name))}


def load(profile_dir: str, hlo_text: str) -> dict:
    """`bench.trace.load`'s reduced form, plus ``"paths"`` per device
    (from ``hlo_text``, the compiled HLO of the program the trace ran)
    and the ``sky.*`` host spans; ``"unnamed_ops"`` counts the device ops
    that are not the program's (the harness's own small programs)."""
    from jax.profiler import ProfileData

    from bench import trace as btrace
    tr = btrace.load(profile_dir)
    names = hlo_op_names(hlo_text)
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    prof = ProfileData.from_file(path)
    paths: dict[str, list[str]] = {}
    for plane in prof.planes:
        if btrace.CHIP_PLANE.fullmatch(plane.name):
            paths[plane.name] = [
                names.get(btrace.op_name(e.name), "")
                for line in plane.lines if line.name in btrace.OP_LINES
                for e in line.events]
        elif plane.name.startswith("/host:"):
            tr["host"] += [[e.name, e.start_ns, e.duration_ns]
                           for line in plane.lines for e in line.events
                           if e.name.startswith(SCOPE_PREFIX)]
    for dev in tr["devices"]:
        dev["paths"] = paths[dev["name"]]
    tr["unnamed_ops"] = sum(not p for d in tr["devices"] for p in d["paths"])
    return tr


def _scoped(tr: dict, scope, prefixes: tuple[str, ...]):
    """Per device, its events under ``scope`` (None: under no scope)."""
    for dev in tr["devices"]:
        yield [e for e, p in zip(dev["events"], dev["paths"])
               if scope_of(p) == scope
               and (not prefixes or e[0].startswith(prefixes))]


def scope_seconds(tr: dict, scope, prefixes: tuple[str, ...] = ()):
    """Device seconds under ``scope`` inside the window, summed over
    devices; None where the trace carries no op paths.  ``scope=None``
    is the busy time that no op under a scope covers (an unnamed op
    inside a scoped loop is that loop's time)."""
    from bench import trace as btrace
    if not tr["devices"] or "paths" not in tr["devices"][0]:
        return None
    lo, hi = btrace.window(tr)

    def span(events):
        return sum(b - a for a, b in btrace._union(events, lo, hi))

    if scope is not None:
        return sum(span(evs) for evs in _scoped(tr, scope, prefixes)) * 1e-9
    total = 0.0
    for dev in tr["devices"]:
        named = [e for e, p in zip(dev["events"], dev["paths"])
                 if scope_of(p)]
        unnamed = [e for e, p in zip(dev["events"], dev["paths"])
                   if not scope_of(p)
                   and (not prefixes or e[0].startswith(prefixes))]
        total += span(named + unnamed) - span(named)
    return total * 1e-9


def scopes(tr: dict) -> list[str]:
    """The stage scopes that name some op of the trace, sorted."""
    return sorted({s for dev in tr["devices"] for p in dev.get("paths", ())
                   if (s := scope_of(p))})


def scope_ops(tr: dict, scope, k: int = 5) -> list[list]:
    """[[op family, seconds per device], ...] of the ops under ``scope``
    that started in the window, the ``k`` largest."""
    from bench import trace as btrace
    lo, hi = btrace.window(tr)
    ndev = max(len(tr["devices"]), 1)
    tot: dict[str, float] = {}
    for events in _scoped(tr, scope, ()):
        for n, s, d in events:
            if lo <= s < hi:
                fam = btrace.op_family(n)
                tot[fam] = tot.get(fam, 0.0) + d * 1e-9 / ndev
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def span_seconds(tr: dict, name: str) -> list[float]:
    """Durations of the host spans called ``name`` that start in the
    window."""
    from bench import trace as btrace
    lo, hi = btrace.window(tr)
    return [d * 1e-9 for n, s, d in tr["host"] if n == name and lo <= s < hi]


def counters(answers: list) -> dict[str, float]:
    """Sum of each work counter over the answers' stats, and the answers
    that carry it."""
    out: dict[str, float] = {}
    for _, (_, stats) in answers:
        for k in COUNTERS:
            if k in stats:
                out[k] = out.get(k, 0) + float(np.asarray(stats[k]))
                out[k + "_answers"] = out.get(k + "_answers", 0) + 1
    return out


def collective_prefixes() -> tuple[str, ...]:
    """The op-name prefixes `collective_ms.x4` counts as collectives."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "metrics", "collective_ms.x4.py")
    spec = importlib.util.spec_from_file_location("bench_collectives", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PREFIXES


def split(tr: dict, completed: int, prefixes: tuple[str, ...]) -> dict:
    """The per-stage reduction of one traced window."""
    from bench import trace as btrace
    ms = lambda s: None if s is None else s * 1e3 / completed  # noqa: E731
    busy = sum(btrace.busy(tr))
    unscoped = scope_seconds(tr, None)
    if unscoped is None:
        return {"busy_ms": ms(busy)}
    found = scopes(tr)
    stage_ms = {s: ms(scope_seconds(tr, s)) for s in found}
    disp = span_seconds(tr, DISPATCH_SPAN)
    return {
        "busy_ms": ms(busy),
        "stage_ms": stage_ms,
        "unscoped_ms": ms(unscoped),
        "stages_plus_unscoped_over_busy":
            (sum(scope_seconds(tr, s) for s in found) + unscoped) / busy
            if busy else None,
        "unscoped_share": unscoped / busy if busy else None,
        "collective_ms": {s: ms(scope_seconds(tr, s, prefixes))
                          for s in found + [None]},
        "stage_ops": {s: scope_ops(tr, s) for s in found + [None]},
        "dispatch_ms": sum(disp) * 1e3 / len(disp) if disp else None,
        "dispatch_ms_median": statistics.median(disp) * 1e3 if disp else None,
        "dispatch_spans": len(disp),
        "idle_gaps": btrace.idle_by_host_span(tr),
        "unnamed_ops": tr.get("unnamed_ops"),
    }


@contextlib.contextmanager
def cache_keeps_metadata():
    """While open, JAX's compile cache keys hold the op metadata, so an
    executable read back carries this program's scopes; and op locations
    keep one frame (the program's own line), so the HLO text that
    `stage_run` reads back is the cache entry of the queries' executable.
    (Dropping whole tracebacks from locations instead also drops scopes
    from many op names.)"""
    import jax
    keys = {"jax_compilation_cache_include_metadata_in_key": True,
            "jax_traceback_in_locations_limit": 1}
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def stage_run(reg, name: str, seed: int, seconds: float, devices, *,
              save: str = "") -> dict:
    """One untraced and one traced window of cell ``name``: the result
    object (not printed).  With ``save``, that directory receives the
    program's compiled HLO text (``hlo.txt``) and the extended reduced
    trace (``trace.json.gz``)."""
    import jax

    from bench import loops, systems
    from repro.core import parallel
    cell = reg.cell(name)
    conf = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    system = systems.SYSTEMS[conf["system"]](conf, traffic, devices, seed)
    system.warm()
    # the executable the queries run, read back from the compile cache
    hlo = parallel.fused_skyline_fn(system.cfg, system.mesh).lower(
        system.tables[0], system.mask, system.key).compile().as_text()
    if save:
        with open(os.path.join(save, "hlo.txt"), "w") as f:
            f.write(hlo)
    loop = loops.LOOPS[traffic["loop"]]
    untraced = loop(system, traffic, seed, seconds,
                    lambda span: contextlib.nullcontext())
    with tempfile.TemporaryDirectory(prefix="bench_stages_") as tdir:
        jax.profiler.start_trace(tdir)
        try:
            traced = loop(system, traffic, seed, seconds,
                          jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        tr = load(tdir, hlo)
    if save:
        from bench import trace as btrace
        btrace.save(tr, os.path.join(save, "trace.json.gz"))
    completed = traced.attempted - traced.failed
    out = {"cell": name, "seed": seed, "device": devices[0].device_kind,
           "chips": len(devices),
           "program_scopes": list(getattr(parallel, "STAGE_SCOPES", ())),
           "windows": {
               k: {"queries": w.attempted, "window_s": w.seconds,
                   "s_per_query": w.seconds / max(w.attempted, 1)}
               for k, w in (("untraced", untraced), ("traced", traced))},
           "counters": counters(traced.answers),
           **split(tr, completed, collective_prefixes())}
    ctx = {"trace": tr, "completed": completed, "window_s": traced.seconds,
           "chips": len(devices)}
    out["per_layer"] = {m["name"]: reg.reader(m["name"])(ctx)
                        for m in reg.metrics("per_layer", name)}
    return out


def main(argv=None) -> int:
    from bench import run as brun
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default="",
                    help="directory that receives the program's compiled "
                         "HLO text and the extended reduced trace")
    args = ap.parse_args(argv)
    try:
        reg = brun.Registry(brun.ROOT)
        devices = brun.find_devices(reg.cell(args.workload)["chips"])
    except brun.BenchError as e:
        print(f"stages: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    cache = brun.use_compile_cache()
    with cache_keeps_metadata():
        out = stage_run(reg, args.workload, args.seed, args.seconds,
                        devices, save=args.save)
    print(json.dumps(dict(out, compile_cache=cache)), flush=True)
    return 0


if __name__ == "__main__":
    _here = os.path.dirname(os.path.abspath(__file__))
    if os.path.abspath(sys.path[0]) == _here:
        sys.path.pop(0)  # bench/ itself: its trace.py is not stdlib trace
    _root = os.path.dirname(_here)
    sys.path[:0] = [_root, os.path.join(_root, "src")]
    sys.exit(main())
