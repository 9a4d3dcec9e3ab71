"""The harness finds cells, configurations, traffic mixes and metric
readers by name: a throw-away cell made of new files only runs."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402

from bench import run  # noqa: E402


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return run.Registry(bench_tiny.make_root(tmp_path_factory.mktemp("b")))


def test_lookup_by_name(reg):
    assert reg.cell("tiny.closedmix")["config"] == "tiny"
    assert reg.config("tiny")["n"] == 3000
    assert reg.traffic("closedmix")["loop"] == "closed"
    assert callable(reg.reader("busy_ms.tiny"))
    with pytest.raises(run.BenchError):
        reg.cell("no.such")
    with pytest.raises(run.BenchError):
        reg.reader("no_such_metric")


def test_metric_lists_follow_workloads_and_moves(reg):
    names = lambda kind, cell: [m["name"] for m in reg.metrics(kind, cell)]
    assert names("end_to_end", "tiny.closedmix") == ["tuples_per_s",
                                                     "setup_s"]
    # a per-layer metric without `workloads` goes to every cell that
    # reports the end-to-end metric it moves
    assert names("per_layer", "tiny.closedmix") == ["busy_ms.tiny"]
    assert names("per_layer", "tiny4.closedmix") == ["busy_ms.tiny"]


def test_throwaway_cell_runs_from_new_files(reg):
    devices = run.find_devices(1, require_tpu=False)
    res = run.run_cell(reg, "tiny.closedmix", 3, 0.5, False, devices)
    assert res["correct"] and res["attempted"] >= 1
    assert set(res["metrics"]) == {"tuples_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    json.dumps(res)


def test_checkout_files_parse():
    """Every cell of the checkout finds its files and readers."""
    reg = run.Registry(bench_tiny.ROOT)
    for cell in reg.spec["workloads"]:
        assert reg.config(cell["config"])["chips"] == cell["chips"]
        assert reg.traffic(cell["traffic"])["loop"] in ("closed",)
    for m in reg.spec["per_layer"]:
        assert callable(reg.reader(m["name"]))


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_benchmark_json_keeps_its_shape():
    """The static rules every later cell and metric has to keep."""
    spec = run.Registry(bench_tiny.ROOT).spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(bench_tiny.ROOT, p))
    cells = {c["name"]: c for c in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    assert configs == {c["config"] for c in cells.values()}
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
    reg = run.Registry(bench_tiny.ROOT)
    for m in spec["per_layer"]:
        for c in m.get("workloads", []):
            assert m["moves"] in [x["name"] for x in
                                  reg.metrics("end_to_end", c)]
    for c in cells:
        assert NAME.fullmatch(c)
        ends = [m["name"] for m in reg.metrics("end_to_end", c)]
        assert "setup_s" in ends and len(ends) >= 2
        assert reg.metrics("per_layer", c)
