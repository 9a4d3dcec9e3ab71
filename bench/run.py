"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in `BENCHMARK.json`: a configuration
(``bench/configs/<config>.json``, found through the ``configs`` entry of
that name) under a traffic mix (``bench/traffic/<traffic>.json``).  A
per-layer metric is a reader ``bench/metrics/<metric>.py`` with a
function ``read(ctx)``.  Nothing here names a cell, a configuration, a
mix or a metric: a new one is new files and entries.

The run fails (exit 1, no result) when JAX finds no TPU or fewer chips
than the cell asks for, or when the checkout holds no program.  It keeps
JAX's compilation cache at ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), builds the system and its data on
the device from ``--seed``, warms the cell's shapes, measures for
``--seconds``, and holds the window's answers to the plain reference.
``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
    """A run that cannot give a result."""


def say(**fields) -> None:
    print("bench: " + json.dumps(fields, default=str), flush=True)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

class Registry:
    """Cells, configurations, traffic mixes and metric readers, found by
    name under ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        path = os.path.join(root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise BenchError(f"no BENCHMARK.json in {root}")
        with open(path) as f:
            self.spec = json.load(f)

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise BenchError(f"no {key} entry named {name!r}")

    def _json(self, rel: str) -> dict:
        path = os.path.join(self.root, rel)
        if not os.path.exists(path):
            raise BenchError(f"missing {rel}")
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return self._json(self._named("configs", name)["file"])

    def traffic(self, name: str) -> dict:
        return self._json(os.path.join("bench", "traffic", f"{name}.json"))

    def metrics(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
        out = []
        for m in self.spec[kind]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or any(
                    e["name"] == m["moves"]
                    for e in self.metrics("end_to_end", cell)):
                out.append(m)
        return out

    def reader(self, metric: str):
        path = os.path.join(self.root, "bench", "metrics", f"{metric}.py")
        if not os.path.exists(path):
            raise BenchError(f"no reader for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def use_compile_cache() -> str:
    """Keep every program of the run in the persistent cache."""
    import jax
    path = os.environ.get(CACHE_ENV) or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


@contextlib.contextmanager
def compile_watch(counts: dict):
    """Counts traces and backend compiles (or cache loads) into
    ``counts`` while open."""
    import jax.monitoring
    from jax._src import dispatch
    names = {dispatch.JAXPR_TRACE_EVENT: "traces",
             dispatch.BACKEND_COMPILE_EVENT: "compiles"}
    counts.update(traces=0, compiles=0)

    def listen(event, duration, **kw):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def program_counts() -> dict:
    from repro.core.parallel import trace_count
    from repro.serve.engine import pack_trace_count
    return {"trace_count": trace_count("fused"),
            "trace_count_batch": trace_count("fused_batch"),
            "pack_trace_count": pack_trace_count()}


def sweep_geometry(since: int = 0) -> list[dict]:
    from repro.kernels.sfs.ops import traced_geometries
    return [{k: g[k] for k in ("p", "n", "d", "block", "wcap", "wtile",
                               "vmem_limit")}
            for g in traced_geometries()[since:] if not g["interpret"]]


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def _span_factory(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(reg: Registry, name: str, seed: int, seconds: float,
             trace: bool, devices, *, t_start: float = T_START) -> dict:
    """One run of cell ``name``: its result object (not printed)."""
    import jax

    from bench import loops, systems
    from bench import trace as btrace
    cell = reg.cell(name)
    conf = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    system = systems.SYSTEMS[conf["system"]](conf, traffic, devices, seed)
    system.warm()
    say(phase="setup_done", sweep_geometry=sweep_geometry())
    before = program_counts()
    span = _span_factory(trace)
    loop = loops.LOOPS[traffic["loop"]]
    tr = None
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        if trace:
            jax.profiler.start_trace(tdir)
        setup_s = time.monotonic() - t_start
        try:
            with compile_watch({}) as in_window:
                win = loop(system, traffic, seed, seconds, span)
        finally:
            if trace:
                jax.profiler.stop_trace()
        if trace:
            tr = btrace.load(tdir)
    after = program_counts()
    in_window.update({k: after[k] - before[k] for k in after})
    say(phase="window_done", compiles_in_window=in_window,
        attempted=win.attempted, failed=win.failed,
        window_s=win.seconds, **win.log)
    memory = peak_bytes(devices)

    t_check = time.monotonic()
    compared = system.check(win.answers, seed, traffic).compared()
    correct = systems.holds(compared)
    say(phase="check_done", check_s=time.monotonic() - t_check)

    metrics: dict[str, dict] = {}
    if not trace:
        values = dict(win.e2e, setup_s=setup_s)
        for m in reg.metrics("end_to_end", name):
            if m["name"] not in values:
                raise BenchError(f"cell {name} does not measure "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {"trace": tr, "completed": win.attempted - win.failed,
               "window_s": win.seconds, "chips": len(devices)}
        for m in reg.metrics("per_layer", name):
            v = reg.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if tr is not None:
        busy = btrace.busy(tr)
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        lo, hi = btrace.window(tr)
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {"device_ops": btrace.top_ops(tr),
                               "idle_gaps": btrace.idle_by_host_span(tr)}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        reg = Registry(ROOT)
        cell = reg.cell(args.workload)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError("no program (src/repro) in this checkout")
        sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
        devices = find_devices(cell["chips"])
        say(cell=args.workload, seed=args.seed, seconds=args.seconds,
            trace=args.trace, compile_cache=use_compile_cache(),
            device_kind=devices[0].device_kind)
        result = run_cell(reg, args.workload, args.seed, args.seconds,
                          bool(args.trace), devices)
    except BenchError as e:
        print(f"bench: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for k, v in result["compared"].items():
        print(f"compared {k}: {v}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
        sys.path.pop(0)  # bench/ itself: its trace.py is not stdlib trace
    sys.exit(main())
