"""Stage scopes of the one-shot program (`repro.core.parallel.STAGE_SCOPES`).

Each stage of the pipeline runs under a `jax.named_scope`, so every op
the program traces carries exactly one stage in its HLO ``op_name``, and
on a workers mesh each collective falls under the stage that issues it:
the partition shuffle under ``sky.partition``, the flat or tree merge's
under ``sky.merge``.  Ops the compiler makes up itself carry no source
frame in their metadata (a rewritten reduce-window, a broadcast the
partitioner splits off) and are left out.  Each Pallas kernel runs under
the stage of its work: NoSeq's phase-2 test (``noseq_mask``) under
``sky.merge``, apart from the representative filter's ``dominated_mask``.
The host work of a one-shot call runs under the profiler span
``sky.dispatch``.
"""

import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.core import parallel
from repro.core.parallel import SkyConfig, fused_skyline_fn

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = dict(strategy="sliced", p=8, rep_filter="sorted", local_capacity=256,
           capacity=1024, block=64)
N, D = 2048, 4
IGNORED = {"parameter", "tuple", "get-tuple-element", "constant", "bitcast",
           "copy", "copy-start", "copy-done"}
COLLECTIVES = {"all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter"}

_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INST = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(r"\b(condition|body|to_apply|branch_computations|"
                     r"true_computation|false_computation)="
                     r"(?:\{([^}]*)\}|%?([\w.\-]+))")


def _instructions(text: str):
    """(computation, instruction name, opcode, attributes) of every
    instruction of the compiled module's text; and the entry."""
    comp, entry, out = None, None, []
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            comp = head.group(2)
            entry = comp if head.group(1) else entry
        elif line.startswith("}"):
            comp = None
        elif comp is not None and (inst := _INST.match(line)):
            name, rest = inst.groups()
            # the opcode follows the result shape, before its operands
            shape_end = rest.index(" ") if not rest.startswith("(") else \
                rest.index(") ") + 1
            op = _OPCODE.match(rest[shape_end:])
            out.append((comp, name, op.group(1) if op else "", rest))
    return entry, out


def executed_ops(text: str):
    """[(name, opcode, op_name or None, traced from source)] of the
    instructions the device runs as ops: those of the entry computation
    and of the loops, branches and calls it reaches (not the insides of
    fusions, sorts' comparators or reductions)."""
    entry, insts = _instructions(text)
    by_comp: dict[str, list] = {}
    for comp, *rest in insts:
        by_comp.setdefault(comp, []).append(rest)
    seen, todo, out = set(), [entry], []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, rest in by_comp[comp]:
            for key, braced, single in _CALLED.findall(rest):
                if key != "to_apply" or op == "call":
                    todo += [c.strip().lstrip("%")
                             for c in (braced or single).split(",")]
            meta = re.search(r"metadata=\{([^}]*)\}", rest)
            meta = meta.group(1) if meta else ""
            on = re.search(r'op_name="([^"]*)"', meta)
            out.append((name, op, on.group(1) if on else None,
                        "stack_frame_id=" in meta))
    return out


def stages_of(op_name):
    return [p for p in (op_name or "").split("/") if p.startswith("sky.")]


def check_stages(text: str) -> dict[str, dict[str, int]]:
    """Every traced op carries exactly one stage; returns, per stage, the
    count of each collective opcode under it."""
    colls: dict[str, dict[str, int]] = {s: {} for s in parallel.STAGE_SCOPES}
    found = set()
    for name, op, op_name, traced in executed_ops(text):
        if op in IGNORED:
            continue
        stages = stages_of(op_name)
        if traced or op in COLLECTIVES:
            assert len(stages) == 1, (name, op, op_name)
            found.add(stages[0])
        if op in COLLECTIVES:
            colls[stages[0]][op] = colls[stages[0]].get(op, 0) + 1
    assert found == set(parallel.STAGE_SCOPES)
    return colls


def pallas_kernels(jaxpr, stack=()):
    """[(kernel name, stages around it)] of every `pallas_call` of a
    jaxpr and of the jaxprs inside it."""
    out = []
    for eqn in jaxpr.eqns:
        here = stack + tuple(str(eqn.source_info.name_stack).split("/"))
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(p for p in here if p.startswith("sky."))))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    out += pallas_kernels(sub, here)
    return out


def program_kernels(cfg: SkyConfig, n: int, d: int) -> dict[str, set]:
    """kernel name -> the stages it runs under, in the one-shot program."""
    jaxpr = jax.make_jaxpr(fused_skyline_fn(cfg))(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.bool_), jax.random.PRNGKey(0))
    out: dict[str, set] = {}
    for name, stages in pallas_kernels(jaxpr.jaxpr):
        out.setdefault(name, set()).add(stages)
    return out


def test_stage_names():
    assert parallel.STAGE_SCOPES == ("sky.partition", "sky.rep_filter",
                                     "sky.local", "sky.merge")
    assert parallel.DISPATCH_SPAN == "sky.dispatch"


@pytest.mark.parametrize("noseq", [False, True])
def test_one_device_program_ops_carry_one_stage(noseq):
    pts = jax.ShapeDtypeStruct((N, D), jnp.float32)
    mask = jax.ShapeDtypeStruct((N,), jnp.bool_)
    text = fused_skyline_fn(SkyConfig(noseq=noseq, **CFG)).lower(
        pts, mask, jax.random.PRNGKey(0)).compile().as_text()
    colls = check_stages(text)
    assert all(not c for c in colls.values())
    kernels = program_kernels(
        SkyConfig(noseq=noseq, impl="interpret", **CFG), N, D)
    merge_sweep = set() if noseq else {("sky.merge",)}
    assert kernels == {
        "dominated_mask": {("sky.rep_filter",)},
        "sfs_sweep": {("sky.local",)} | merge_sweep,
        **({"noseq_mask": {("sky.merge",)}} if noseq else {})}


FOUR = r'''
import sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.parallel import SkyConfig, fused_skyline_fn
from repro.launch.mesh import make_worker_mesh
mesh = make_worker_mesh(4)
sh = NamedSharding(mesh, P("workers"))
pts = jax.ShapeDtypeStruct(({n}, {d}), jnp.float32, sharding=sh)
mask = jax.ShapeDtypeStruct(({n},), jnp.bool_, sharding=sh)
cfg = SkyConfig(merge={merge!r}, noseq={noseq!r}, **{cfg!r})
text = fused_skyline_fn(cfg, mesh).lower(
    pts, mask, jax.random.PRNGKey(0)).compile().as_text()
open({out!r}, "w").write(text)
'''


@pytest.mark.parametrize("noseq", [False, True])
@pytest.mark.parametrize("merge", ["flat", "tree"])
def test_four_worker_collectives_fall_under_their_stage(tmp_path, merge,
                                                        noseq):
    out = str(tmp_path / "hlo.txt")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = FOUR.format(n=N, d=D, merge=merge, noseq=noseq, cfg=CFG,
                       out=out)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    colls = check_stages(open(out).read())
    # the partition stage sorts and gathers the whole table: its shuffle
    assert colls["sky.partition"]
    assert "collective-permute" not in colls["sky.partition"]
    assert colls["sky.rep_filter"].get("all-gather")
    assert not colls["sky.local"]
    if merge == "flat":
        assert colls["sky.merge"].get("all-gather")
        assert "collective-permute" not in colls["sky.merge"]
    else:
        # two ppermute rounds at W=4, then the root's psum broadcast
        assert colls["sky.merge"].get("collective-permute") == 2
        assert colls["sky.merge"].get("all-reduce")


def test_dispatch_span_in_the_profile(tmp_path):
    from jax.profiler import ProfileData

    pts = jax.random.uniform(jax.random.PRNGKey(3), (512, 3))
    cfg = SkyConfig(strategy="sliced", p=4, capacity=256, block=64)
    jax.block_until_ready(parallel.parallel_skyline(pts, cfg=cfg))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(parallel.parallel_skyline(pts, cfg=cfg))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [e for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == parallel.DISPATCH_SPAN]
    assert len(spans) == 1 and spans[0].duration_ns > 0


def test_sweep_block_counters_match_the_kernel_bound(monkeypatch):
    """`sweep_blocks` counts the candidate blocks the local sweep runs —
    each partition's up to its last valid row after the representative
    filter, read from the mask the sweep receives — and
    `merge_sweep_blocks` those of the sequential merge's sweep over the
    compacted union."""
    import numpy as np
    from repro.core import sfs
    from repro.kernels.sfs.ops import sweep_blocks

    seen = []
    real = sfs.sfs_sweep

    def spy(pts_p, mask_p, **kw):
        seen.append((np.asarray(mask_p), kw["block"]))
        return real(pts_p, mask_p, **kw)

    monkeypatch.setattr(sfs, "sfs_sweep", spy)
    cfg = SkyConfig(**CFG)
    pts = jax.random.uniform(jax.random.PRNGKey(5), (N, D))
    key = jax.random.PRNGKey(0)
    buckets, meta, _ = parallel.partition_stage(pts, jnp.ones(N, bool),
                                                cfg, key)
    sky, s2 = parallel.local_stage(buckets.points, buckets.mask, cfg,
                                   key=jax.random.fold_in(key, 1))
    _, s3 = parallel.merge_stage(sky, meta, cfg)
    (local_mask, block), (merge_mask, merge_block) = seen
    assert block == merge_block == CFG["block"]

    valid = local_mask.sum(axis=1)
    assert int(s2["rep_filter_dropped"]) > 0
    assert int(s2["sweep_blocks"]) == int(np.sum(-(-valid // block)))
    assert int(s2["sweep_blocks"]) == int(np.sum(sweep_blocks(local_mask,
                                                              block)))
    padded = local_mask.shape[0] * local_mask.shape[1] // block
    assert int(s2["sweep_blocks"]) < padded

    union = int(s3["union_size"])
    assert int(merge_mask.sum()) == union
    assert int(s3["merge_sweep_blocks"]) == -(-union // block)
    assert int(s3["merge_sweep_blocks"]) == int(np.sum(sweep_blocks(
        merge_mask, block)))
