"""`bench/run.py` end to end on the CPU: it refuses to run without a TPU
or without the program, and with the timed path broken underneath, the
rest of a run reads `correct` false."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402

from bench import run  # noqa: E402


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hou7.oneshot",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = _bench_cmd(bench_tiny.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    p = _bench_cmd(bench_tiny.copy_checkout_bench(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return run.Registry(bench_tiny.make_root(tmp_path_factory.mktemp("b")))


def _run(reg, cell, trace=False):
    devices = run.find_devices(1, require_tpu=False)
    res = run.run_cell(reg, cell, 2**31 + 11, 0.6, trace, devices)
    json.dumps(res)
    return res


def _alter(buf):
    """The answer altered where it is produced: one member moved."""
    return buf._replace(points=buf.points.at[0, 0].add(1e-3))


def test_sound_traced_run_is_correct(reg):
    res = _run(reg, "tiny.closedmix", trace=True)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == set()  # CPU: no device trace to read
    assert res["device"]["window_s"] > 0


def test_oneshot_answer_altered(reg, monkeypatch):
    from repro.core import parallel
    orig = parallel.parallel_skyline

    def broken(*a, **kw):
        buf, stats = orig(*a, **kw)
        return _alter(buf), stats

    monkeypatch.setattr(parallel, "parallel_skyline", broken)
    res = _run(reg, "tiny.closedmix")
    assert not res["correct"]
    assert res["compared"]["missing_rows"]["value"] > 0


@pytest.mark.parametrize("fault, caught", [
    ("half_rows", ("missing_rows", "extra_rows")),
    ("stale", ("missing_rows", "extra_rows")),
    ("count", ("count_mismatch",)),
    ("overflow", ("overflow_flags",)),
])
def test_oneshot_faults(reg, monkeypatch, fault, caught):
    """Half of each table left out of the query, every query answered with
    the first query's answer (a state that never moves on), a count that
    disagrees with the rows, or an overflow reported."""
    from repro.core import parallel
    orig = parallel.parallel_skyline
    first = []

    def broken(pts, mask, **kw):
        if fault == "half_rows":
            return orig(pts, mask.at[::2].set(False), **kw)
        buf, stats = orig(pts, mask, **kw)
        if fault == "count":
            return buf._replace(count=buf.count + 1), stats
        if fault == "overflow":
            return buf._replace(overflow=buf.overflow | True), stats
        first.append((buf, stats))
        return first[0]

    monkeypatch.setattr(parallel, "parallel_skyline", broken)
    res = _run(reg, "tiny.closedmix")
    assert res["attempted"] > 1
    assert not res["correct"]
    assert sum(res["compared"][k]["value"] for k in caught) > 0


SHARDED = r'''
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {here!r}]
import jax, jax.numpy as jnp
import bench_tiny
from bench import run
reg = run.Registry(bench_tiny.make_root({tmp!r}))
devices = run.find_devices(4, require_tpu=False)
if {broken!r}:
    # the exchange between chips left out: a gather returns this chip's
    # own rows in every slot, a permute delivers nothing
    def gather(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.axis_size(axis_name)
        return (jnp.concatenate([x] * n, axis) if tiled
                else jnp.stack([x] * n, axis))
    jax.lax.all_gather = gather
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
res = run.run_cell(reg, "tiny4.closedmix", 7, 0.5, False, devices)
print("RESULT", res["correct"], res["compared"]["missing_rows"]["value"]
      + res["compared"]["extra_rows"]["value"])
'''


@pytest.mark.parametrize("broken", [False, True])
def test_sharded_cell_needs_the_exchange(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(root=bench_tiny.ROOT, tmp=str(tmp_path),
                          here=os.path.dirname(os.path.abspath(__file__)),
                          broken=broken)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT")]
    assert line, p.stderr[-3000:]
    _, correct, wrong = line[0].split()
    assert correct == str(not broken)
    assert (int(wrong) > 0) == broken
