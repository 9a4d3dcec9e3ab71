"""The static verifier (`repro.analysis`): each skylint rule fires on
its fixture violation (and ONLY there), suppressions and the baseline
are honored, the real tree gates clean, and the Layer-2 program
verifier holds its invariants on the traced suite."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.findings import load_baseline, write_baseline
from repro.analysis.lint import lint_paths

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def _write(tmp_path, rel, code):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return str(path)


# one minimal violation per rule: (rule, relpath, source, violation line)
FIXTURES = {
    "R1": ("pipe/hot.py", """\
        import jax


        @jax.jit
        def entry(x):
            return helper(x)


        def helper(x):
            return x.item() + 1
        """, 10),
    "R2": ("serve/packer.py", """\
        import jax.numpy as jnp


        def pack(items):
            out = []
            for it in items:
                out.append(jnp.pad(it, (0, 3)))
            return out
        """, 7),
    "R3": ("pipe/caller.py", """\
        from repro.kernels.sfs.ops import sfs_sweep

        print(sfs_sweep)
        """, 1),
    "R4": ("pipe/meshy.py", """\
        from jax.experimental.shard_map import shard_map

        print(shard_map)
        """, 1),
    "R5": ("core/branchy.py", """\
        import jax
        import jax.numpy as jnp


        @jax.jit
        def entry(x):
            if jnp.max(x) > 0:
                return x
            return -x
        """, 7),
    "R6": ("serve/statefact.py", """\
        import jax


        def update_fn():
            def run(state, x):
                return state + x

            return jax.jit(run)
        """, 8),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_each_rule_fires_exactly_on_its_fixture(tmp_path, rule):
    rel, code, line = FIXTURES[rule]
    path = _write(tmp_path, rel, code)
    findings = lint_paths([str(tmp_path)], repo_root=str(tmp_path))
    active = [f for f in findings if f.active]
    assert len(active) == 1, [str(f) for f in findings]
    f = active[0]
    assert f.rule == rule
    assert os.path.join(str(tmp_path), f.path) == path
    assert f.line == line
    assert f.hint  # every rule ships a fix-hint


def test_fixtures_do_not_cross_fire(tmp_path):
    """All fixtures together: one active finding per rule — no rule
    fires on another rule's fixture."""
    for rule, (rel, code, _) in FIXTURES.items():
        _write(tmp_path, rel, code)
    findings = [f for f in lint_paths([str(tmp_path)],
                                      repo_root=str(tmp_path)) if f.active]
    assert sorted(f.rule for f in findings) == sorted(FIXTURES)


def test_suppression_comment_same_line_and_line_above(tmp_path):
    rel, code, line = FIXTURES["R1"]
    code = code.replace("return x.item() + 1",
                        "return x.item() + 1  # skylint: disable=R1")
    _write(tmp_path, rel, code)
    rel4, code4, _ = FIXTURES["R4"]
    code4 = code4.replace(
        "from jax.experimental.shard_map import shard_map",
        "# legacy path kept for a vendored script\n"
        "        # skylint: disable=R4\n"
        "        from jax.experimental.shard_map import shard_map", 1)
    _write(tmp_path, rel4, code4)
    findings = lint_paths([str(tmp_path)], repo_root=str(tmp_path))
    assert len(findings) == 2
    assert all(f.suppressed and not f.active for f in findings)
    # a suppression for a DIFFERENT rule does not cover the finding
    _write(tmp_path, "pipe/wrong.py", """\
        import jax


        @jax.jit
        def entry(x):
            return helper(x)


        def helper(x):
            return x.item() + 1  # skylint: disable=R2
        """)
    findings = lint_paths([str(tmp_path / "pipe" / "wrong.py")],
                          repo_root=str(tmp_path))
    assert [f.rule for f in findings if f.active] == ["R1"]


def test_baseline_grandfathers_by_line_text(tmp_path):
    rel, code, _ = FIXTURES["R3"]
    _write(tmp_path, rel, code)
    first = lint_paths([str(tmp_path)], repo_root=str(tmp_path))
    bl = tmp_path / "baseline.json"
    assert write_baseline(first, str(bl)) == 1
    again = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline_keys=load_baseline(str(bl)))
    assert all(f.baselined and not f.active for f in again)
    # moving the offending line keeps it baselined (keyed on text)...
    _write(tmp_path, rel, "# a new leading comment\n"
           + textwrap.dedent(code))
    moved = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline_keys=load_baseline(str(bl)))
    assert all(f.baselined for f in moved if f.rule == "R3")
    # ...but a CHANGED offending line goes stale and gates again
    _write(tmp_path, rel,
           "from repro.kernels.dominance.ops import dominated_mask\n")
    stale = lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                       baseline_keys=load_baseline(str(bl)))
    assert [f.rule for f in stale if f.active] == ["R3"]


def test_clean_tree_passes():
    """The gate on the real tree: zero active findings AND zero R1
    suppressions — the formerly sanctioned slab fits sync is retired
    (feed now overlays the pending record in-program and polls
    is_ready(); nothing on the serve path blocks on the device)."""
    findings = lint_paths([os.path.join(SRC, "repro")], repo_root=ROOT)
    active = [f for f in findings if f.active]
    assert active == [], [str(f) for f in active]
    r1_suppressed = [f for f in findings
                     if f.suppressed and f.rule == "R1"]
    assert r1_suppressed == [], \
        [str(f) for f in r1_suppressed]


def test_cli_exit_codes_and_json_report(tmp_path):
    """Non-zero exit + a JSON report naming rule and file:line on a
    violation; exit 0 on the clean tree (lint layer: fast, no jax)."""
    rel, code, line = FIXTURES["R1"]
    path = _write(tmp_path, rel, code)
    report = tmp_path / "report.json"
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--layer", "lint",
         "--paths", str(tmp_path), "--json", str(report),
         "--baseline", str(tmp_path / "none.json")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    data = json.loads(report.read_text())
    (f,) = [f for f in data["layers"]["lint"]["findings"]
            if not f["suppressed"]]
    assert f["rule"] == "R1" and f["line"] == line
    # the CLI reports paths relative to the repo root
    assert os.path.normpath(os.path.join(ROOT, f["path"])) == path
    assert not data["ok"]

    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--layer", "lint"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_vmem_estimate_tracks_tiling():
    from repro.kernels.backend import vmem_estimate
    small = vmem_estimate(64, 512)
    big = vmem_estimate(512, 16384)
    assert small["sweep"] < big["sweep"]
    assert small["dominance"] < big["dominance"]
    assert big["window_rows"] == 16384
    # the serving regime (W=4096, BC=512) busts 16 MiB untiled — Mosaic
    # already needs 16.2 MiB at BC=256 (tests/test_tpu_compile.py) —
    # and sits under it at the TPU path's default window tile
    from repro.analysis.verifier import DEFAULT_VMEM_CAP
    from repro.kernels.sfs.ops import TPU_WTILE
    assert vmem_estimate(512, 4096)["sweep"] > DEFAULT_VMEM_CAP
    doc = vmem_estimate(512, 4096, wtile=TPU_WTILE)
    assert doc["sweep"] < DEFAULT_VMEM_CAP
    assert doc["dominance"] < DEFAULT_VMEM_CAP


def test_window_tiling_brings_large_capacity_under_vmem_cap():
    """The acceptance shape: capacity=16384 at block=512 (W x BC = 8.4M
    resident lanes) busts the 16 MiB/core cap untiled, and the SAME
    configuration passes it with a one-block window tile — tiling is
    what admits large windows, not a relaxed cap."""
    from repro.analysis.verifier import DEFAULT_VMEM_CAP
    from repro.kernels.backend import vmem_estimate
    untiled = vmem_estimate(512, 16_384)
    assert untiled["sweep"] > DEFAULT_VMEM_CAP  # previously rejected
    tiled = vmem_estimate(512, 16_384, wtile=512)
    assert tiled["sweep"] < DEFAULT_VMEM_CAP
    assert tiled["window_tile"] == 512
    assert tiled["window_rows"] == untiled["window_rows"] == 16_384
    # the estimate reports the *resident* footprint: tile-width test
    # and append intermediates, never the full window
    assert tiled["sweep"] < untiled["sweep"] / 8


def test_sweep_tiled_cell_passes_layer2_cap():
    """The `sweep_tiled` verifier cell carries the acceptance shape
    through the real Layer-2 gate: it must build, lower, and clear the
    VMEM cap that its untiled twin cannot."""
    from repro.analysis.verifier import verify_programs
    from repro.launch.cells import VERIFIER_EXTRA_CELLS
    spec = VERIFIER_EXTRA_CELLS["sweep_tiled"]
    assert spec["capacity"] == 16_384 and spec["wtile"] == 512
    report, errors = verify_programs(["sweep_tiled"], compile_hlo=False)
    assert errors == [], errors
    est = report["cells"]["sweep_tiled"]["vmem"]
    assert est["window_tile"] == 512
    # the same cell with the tile stripped must FAIL the cap
    untiled = dict(spec, wtile=0)
    from repro.launch.cells import VERIFIER_EXTRA_CELLS as cells_mod
    saved = cells_mod["sweep_tiled"]
    try:
        cells_mod["sweep_tiled"] = untiled
        _, errs = verify_programs(["sweep_tiled"], compile_hlo=False)
    finally:
        cells_mod["sweep_tiled"] = saved
    assert any("exceeds" in e and "sweep" in e for e in errs), errs


def test_program_verifier_invariants_hold():
    """Layer 2 on the traced suite (jaxpr census — no compile, any
    device count): no host primitives, workers-only collectives,
    Q-independence, collective-free vmap path, slab boundary census."""
    from repro.analysis.verifier import verify_programs
    report, errors = verify_programs(compile_hlo=False)
    assert errors == [], errors
    cells = report["cells"]
    assert set(cells) >= {"fused_p512", "batch_8x64", "stream_8x64",
                          "window_8x64", "window_tick", "slab_feed",
                          "slab_wave", "engine_vmap"}
    for name, rec in cells.items():
        assert rec["host_prims"] == [], name
        for prim, by_axis in rec["collectives"].items():
            assert set(by_axis) == {"workers"}, (name, prim, by_axis)
    assert cells["engine_vmap"]["collectives"] == {}
    assert cells["batch_8x64"]["collective_count_q"] == \
        cells["batch_8x64"]["collective_count_2q"]
    # the slab feed's program edge never carries the full state capacity
    from repro.core import SkyConfig
    from repro.core.incremental import state_capacity
    from repro.launch.cells import VERIFIER_EXTRA_CELLS
    spec = VERIFIER_EXTRA_CELLS["slab_feed"]
    cfg = SkyConfig(strategy="sliced", p=spec["p"],
                    capacity=spec["capacity"], block=spec["block"],
                    bucket_factor=1.5)
    assert state_capacity(cfg) not in cells["slab_feed"]["boundary_dims"]
    assert spec["rows"] in cells["slab_feed"]["boundary_dims"]
    # ...and neither does the coalesced serve-loop wave program's (its
    # pending-overlay operands ride at epoch_capacity, not C), and the
    # wave's merge communication is independent of the wave width Q
    wspec = VERIFIER_EXTRA_CELLS["slab_wave"]
    wcfg = SkyConfig(strategy="sliced", p=wspec["p"],
                     capacity=wspec["capacity"], block=wspec["block"],
                     bucket_factor=1.5)
    assert state_capacity(wcfg) not in \
        cells["slab_wave"]["boundary_dims"]
    assert wspec["rows"] in cells["slab_wave"]["boundary_dims"]
    assert cells["slab_wave"]["collective_count_q"] == \
        cells["slab_wave"]["collective_count_2q"]
