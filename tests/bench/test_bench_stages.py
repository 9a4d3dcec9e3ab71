"""The per-stage reduction of `bench/stages.py`: stage scopes, the
program's host span and the work counters, on synthetic and recorded
traces, and one run of the command's windows on the CPU."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402

from bench import run  # noqa: E402
from bench import stages  # noqa: E402
from bench import trace as btrace  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
P = "jit(_fused)/"


@pytest.fixture
def tr():
    """Two devices, window [0, 1000] ns.  Device 0: a partition ``while``
    [100, 300] spanning two partition body ops and an unnamed one, the
    local sweep, the rep filter, merge ops (one clipped by the window's
    end), a partition all-reduce and an unscoped copy; idle [0, 100] and
    [550, 950], the latter inside the program's dispatch span.  Device 1:
    one partition op [200, 300]."""
    ev0 = [
        ["while.1", 100, 200, P + "sky.partition/jit(searchsorted)/while"],
        ["fusion.1", 120, 50, P + "sky.partition/jit(searchsorted)/lt"],
        ["fusion.2", 180, 30, P + "sky.partition/jit(searchsorted)/add"],
        ["reduce-window.1", 250, 20, ""],
        ["sfs_sweep.1", 300, 100, P + "shard_map/sky.local/jit(sfs_sweep)"],
        ["dominated_mask.1", 400, 50, P + "shard_map/sky.rep_filter/x"],
        ["all-gather.1", 450, 20, P + "shard_map/sky.merge/all_gather"],
        ["fusion.3", 470, 30, P + "shard_map/sky.merge/sort"],
        ["all-reduce.1", 500, 40, P + "sky.partition/gather"],
        ["copy.1", 540, 10, ""],
        ["fusion.9", 950, 100, P + "sky.merge/or"],
    ]
    ev1 = [["fusion.1", 200, 100, P + "sky.partition/argsort"]]
    return {
        "devices": [
            {"name": "/device:TPU:0", "events": [e[:3] for e in ev0],
             "paths": [e[3] for e in ev0]},
            {"name": "/device:TPU:1", "events": [e[:3] for e in ev1],
             "paths": [e[3] for e in ev1]}],
        "host": [["bench.window", 0, 1000], ["bench.dispatch", 700, 100],
                 ["sky.dispatch", 720, 60]],
    }


def test_scope_union_counts_a_while_and_its_body_once(tr):
    def s(scope, *prefixes):
        return stages.scope_seconds(tr, scope, prefixes) * 1e9

    assert s("sky.partition") == pytest.approx(200 + 40 + 100)
    assert s("sky.local") == pytest.approx(100)
    assert s("sky.rep_filter") == pytest.approx(50)
    assert s("sky.merge") == pytest.approx(20 + 30 + 50)
    assert s(None) == pytest.approx(10)
    assert stages.scopes(tr) == ["sky.local", "sky.merge", "sky.partition",
                                 "sky.rep_filter"]
    busy = sum(btrace.busy(tr)) * 1e9
    total = sum(s(x) for x in stages.scopes(tr) + [None])
    assert total == pytest.approx(busy)


def test_unnamed_op_inside_a_scoped_loop_is_not_unscoped(tr):
    dev = tr["devices"][0]
    assert ("reduce-window.1", "") in {(e[0], p) for e, p in
                                       zip(dev["events"], dev["paths"])}
    assert stages.scope_seconds(tr, None) == pytest.approx(10e-9)
    assert stages.scope_seconds(tr, None, ("reduce-window",)) == 0
    assert stages.scope_seconds(tr, None, ("copy",)) == pytest.approx(
        10e-9)


def test_scope_collectives_by_prefix(tr):
    coll = ("all-gather", "all-reduce")
    assert stages.scope_seconds(tr, "sky.partition", coll) == \
        pytest.approx(40e-9)
    assert stages.scope_seconds(tr, "sky.merge", coll) == \
        pytest.approx(20e-9)
    assert stages.scope_seconds(tr, "sky.local", coll) == 0


def test_scope_ops_per_device(tr):
    ops = dict(stages.scope_ops(tr, "sky.merge"))
    assert ops == pytest.approx({"fusion": 65e-9, "all-gather": 10e-9})
    assert dict(stages.scope_ops(tr, None)) == pytest.approx(
        {"copy": 5e-9, "reduce-window": 10e-9})


def test_dispatch_span_labels_the_idle_gap_inside_it(tr):
    assert stages.span_seconds(tr, "sky.dispatch") == pytest.approx([60e-9])
    idle = dict(btrace.idle_by_host_span(tr))
    # device 0: [550, 950] (midpoint 750, inside sky.dispatch, the
    # innermost span) and [0, 100]; device 1: [0, 200] and [300, 1000]
    assert idle == pytest.approx({"sky.dispatch": 400e-9 / 2,
                                  "none": (100 + 200 + 700) * 1e-9 / 2})


def test_split_adds_up_to_busy(tr):
    out = stages.split(tr, completed=2, prefixes=("all-",))
    assert out["stages_plus_unscoped_over_busy"] == pytest.approx(1.0)
    assert out["unscoped_share"] == pytest.approx(10 / 600)
    assert out["stage_ms"]["sky.partition"] == pytest.approx(340e-9 * 1e3
                                                             / 2)
    assert out["dispatch_ms"] == pytest.approx(60e-9 * 1e3)
    assert out["dispatch_ms_median"] == pytest.approx(60e-9 * 1e3)
    json.dumps(out)


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="f/sky.merge/add"}
}

%body (p.1: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p.1 = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%p.1), index=1
  %reduce-window.2 = s32[8]{0} reduce-window(%gte.1), window={size=8 pad=7_0}
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(%gte.1, %reduce-window.2)
}

%cond (p.2: (s32[], s32[8])) -> pred[] {
  %p.2 = (s32[], s32[8]{0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%p.2, %p.2), direction=LT
}

ENTRY %main.4 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sort.2 = f32[8]{0} sort(%p), dimensions={0}, metadata={op_name="f/sky.partition/sort"}
  %slice.7 = f32[8]{0} slice(%sort.2), slice={[0:8]}, metadata={op_name="f/shard_map/slice.3"}
  %while.5 = (s32[], s32[8]{0}) while(%t), condition=%cond, body=%body,\
      metadata={op_name="f/sky.local/while"}
  %custom-call.1 = s32[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %loop_add_fusion = f32[8]{0} fusion(%sort.2), kind=kLoop, calls=%fused_computation
}
"""


def test_paths_from_compiled_hlo_text():
    names = stages.hlo_op_names(HLO)
    assert names["sort.2"] == "f/sky.partition/sort"
    # a fusion with no metadata of its own takes its fused root's
    assert names["loop_add_fusion"] == "f/sky.merge/add"
    # a name the partitioner made up gives way to its operand's stage
    assert names["slice.7"] == "f/sky.partition/sort"
    # unnamed ops of a loop's body and condition take the loop's
    assert names["reduce-window.2"] == "f/sky.local/while"
    assert names["compare.1"] == "f/sky.local/while"
    assert "custom-call.1" not in names
    assert stages.scope_of(names["loop_add_fusion"]) == "sky.merge"
    assert stages.scope_of("f/shard_map/broadcast") is None


def _readers():
    reg = run.Registry(run.ROOT)
    return {m["name"]: reg.reader(m["name"]) for m in reg.spec["per_layer"]}


@pytest.mark.parametrize("fixture", ["trace_small.json",
                                     "trace_hou7_tpu.json.gz",
                                     "trace_hou7_stages_tpu.json.gz"])
def test_old_readers_unchanged_and_scopes_absent(fixture):
    """A trace reduced without op paths: every scope reader finds
    nothing, and the benchmark's readers read the same from the extended
    form as from the plain one."""
    tr = btrace.load_json(os.path.join(FIXTURES, fixture))
    plain = {"devices": [{"name": d["name"], "events": d["events"]}
                         for d in tr["devices"]],
             "host": [e for e in tr["host"]
                      if not e[0].startswith(stages.SCOPE_PREFIX)]}
    assert stages.scope_seconds(plain, "sky.partition") is None
    assert stages.scope_seconds(plain, None) is None
    assert "stage_ms" not in stages.split(plain, 2, ("all-",))
    ext = copy.deepcopy(tr)
    if "paths" not in ext["devices"][0]:
        for dev in ext["devices"]:
            dev["paths"] = [P + "sky.local/x"] * len(dev["events"])
        lo, _ = btrace.window(plain)
        ext["host"].append(["sky.dispatch", lo + 1, 5])
    for name, read in _readers().items():
        ctx = {"completed": 2, "window_s": 1.0, "chips": 1}
        assert read(dict(ctx, trace=ext)) == read(dict(ctx, trace=plain)), \
            name


def test_recorded_scoped_tpu_trace_of_two_queries():
    """Two `hou7.oneshot` queries on one v5e, reduced by `load` (op paths
    from the program's compiled HLO), cut to 1.78 s of the window."""
    tr = btrace.load_json(os.path.join(FIXTURES,
                                       "trace_hou7_stages_tpu.json.gz"))
    (busy,) = btrace.busy(tr)
    assert stages.scopes(tr) == ["sky.local", "sky.merge", "sky.partition",
                                 "sky.rep_filter"]
    parts = {s: stages.scope_seconds(tr, s) for s in stages.scopes(tr)}
    unscoped = stages.scope_seconds(tr, None)
    assert sum(parts.values()) + unscoped == pytest.approx(busy, rel=0.01)
    assert unscoped < 0.05 * busy
    # the longest op, the searchsorted loop over the whole table, is the
    # partition stage's; the kernels are the local, merge and filter's
    (dev,) = tr["devices"]
    longest = max(zip(dev["events"], dev["paths"]), key=lambda x: x[0][2])
    assert longest[0][0].startswith("while")
    assert stages.scope_of(longest[1]) == "sky.partition"
    assert "searchsorted" in longest[1]
    kernels = {stages.scope_of(p) for (n, _, _), p in zip(dev["events"],
                                                          dev["paths"])
               if n.startswith(("sfs_sweep", "dominated_mask"))}
    assert kernels == {"sky.local", "sky.merge", "sky.rep_filter"}
    assert len(stages.span_seconds(tr, "sky.dispatch")) >= 1


def test_counters_of_answers():
    answers = [
        (0, (None, {"n_valid": np.int32(100), "rep_filter_dropped":
                    np.int32(60), "union_size": np.int32(12)})),
        (1, (None, {"n_valid": np.int32(50), "rep_filter_dropped":
                    np.int32(10), "union_size": np.int32(8)})),
        (2, (None, {"n_valid": np.int32(50), "union_size": np.int32(4)})),
    ]
    c = stages.counters(answers)
    assert c["n_valid"] == 200 and c["n_valid_answers"] == 3
    assert c["rep_filter_dropped"] == 70
    assert c["rep_filter_dropped_answers"] == 2
    assert c["union_size"] / c["union_size_answers"] == 8


NAMES = r'''
import collections, re, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from bench import stages
from repro.core.parallel import SkyConfig, fused_skyline_fn
cfg = SkyConfig(strategy="sliced", p=4, rep_filter="sorted", capacity=512,
                block=64)
pts = jax.random.uniform(jax.random.PRNGKey(0), (2048, 3))
mask, key = pts[:, 0] >= 0, jax.random.PRNGKey(1)
def names():
    text = fused_skyline_fn(cfg).lower(pts, mask, key).compile().as_text()
    return collections.Counter(re.findall(r'op_name="([^"]*)"', text))
plain = names()
jax.clear_caches()
with stages.cache_keeps_metadata():
    kept = names()
print("SAME", plain == kept, sum(v for k, v in kept.items() if "sky." in k))
'''


def test_cache_settings_keep_the_op_names():
    """What `main` sets around its run leaves every op name of the
    compiled program as the default settings give it."""
    code = NAMES.format(root=bench_tiny.ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    line = [x for x in p.stdout.splitlines() if x.startswith("SAME")]
    assert line, p.stderr[-3000:]
    _, same, scoped = line[0].split()
    assert same == "True" and int(scoped) > 0


def test_stage_run_on_cpu(tmp_path):
    """The command's windows on a tiny cell: the CPU has no device plane,
    so only the windows, counters and the program's HLO come back."""
    reg = run.Registry(bench_tiny.make_root(tmp_path / "b"))
    devices = run.find_devices(1, require_tpu=False)
    out = stages.stage_run(reg, "tiny.closedmix", 2**31 + 5, 0.3, devices,
                           save=str(tmp_path))
    json.dumps(out)
    assert out["windows"]["untraced"]["queries"] >= 1
    assert out["windows"]["traced"]["queries"] >= 1
    assert out["counters"]["n_valid"] == 3000 * out["counters"][
        "n_valid_answers"]
    assert out["counters"]["rep_filter_dropped"] > 0
    assert out["program_scopes"] == ["sky.partition", "sky.rep_filter",
                                     "sky.local", "sky.merge"]
    names = stages.hlo_op_names(open(tmp_path / "hlo.txt").read())
    assert {stages.scope_of(p) for p in names.values()} >= set(
        out["program_scopes"])
    assert btrace.load_json(str(tmp_path / "trace.json.gz"))["devices"] == []
