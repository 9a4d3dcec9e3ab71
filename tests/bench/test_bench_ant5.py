"""The `ant5.oneshot` cell: its configuration file parses into the
program's configuration and matches the cell, and the reader of its
NoSeq metric counts only `noseq_mask` ops."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402

from bench import run  # noqa: E402
from bench import trace as btrace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_small.json")


@pytest.fixture(scope="module")
def reg():
    return run.Registry(bench_tiny.ROOT)


def test_ant5_config_matches_its_cell(reg):
    from repro.core.parallel import SkyConfig
    cell = reg.cell("ant5.oneshot")
    conf = reg.config(cell["config"])
    assert conf["chips"] == cell["chips"] == 1
    assert (conf["system"], conf["n"], conf["d"], conf["distribution"],
            conf["dtype"]) == ("oneshot", 1 << 20, 5, "anticorrelated",
                               "float32")
    cfg = SkyConfig(**conf["sky_config"])
    assert (cfg.strategy, cfg.p, cfg.rep_filter, cfg.noseq) == (
        "sliced", 8, "sorted", True)
    for cap in (cfg.local_capacity, cfg.capacity):
        assert cap & (cap - 1) == 0
    assert conf["reduced"] == []
    assert conf["guarantees"] == {"exact_skyline": True, "overflow": "none",
                                  "shed": "none"}
    assert [m["name"] for m in reg.metrics("end_to_end", cell["name"])] == [
        "tuples_per_s", "setup_s"]
    assert {m["name"] for m in reg.metrics("per_layer", cell["name"])} == {
        "device_idle.oneshot", "sfs_sweep_ms.oneshot",
        "dominated_mask_ms.oneshot", "noseq_mask_ms.ant5"}


def test_noseq_reader_counts_only_noseq_ops(reg):
    tr = btrace.load_json(FIXTURE)
    read = reg.reader("noseq_mask_ms.ant5")
    ctx = {"trace": tr, "completed": 2, "window_s": 1e-6, "chips": 2}
    assert read(ctx) is None  # a program without NoSeq's named test
    tr["devices"][0]["events"] += [["noseq_mask.1", 560, 80],
                                   ["noseq_mask.4", 900, 20],
                                   ["noseq_mask.4", 1500, 30]]
    assert read(ctx) == pytest.approx(100e-9 * 1e3 / 2)
    assert read(dict(ctx, completed=0)) is None
    assert read(dict(ctx, trace=None)) is None
    # the representative filter's metric leaves them out
    assert reg.reader("dominated_mask_ms.oneshot")(ctx) == pytest.approx(
        40e-9 * 1e3 / 2)
