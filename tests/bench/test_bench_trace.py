"""The benchmark's trace reduction on a small recorded trace."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402,F401  (puts the checkout on sys.path)

from bench import trace as btrace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_small.json")


@pytest.fixture
def tr():
    return btrace.load_json(FIXTURE)


def test_window_and_busy_union(tr):
    assert btrace.window(tr) == (0, 1000)
    # device 0: [100, 220] (two overlapping ops) + [300, 310] + [500, 540];
    # the copy at 2000 lies outside the window
    assert btrace.busy(tr) == pytest.approx([170e-9, 1000e-9])


def test_events_by_name_prefix(tr):
    assert btrace.op_seconds(tr, ("sfs_sweep",)) == pytest.approx(100e-9)
    coll = ("all-gather", "collective-permute", "all-reduce")
    assert btrace.op_seconds(tr, coll) == pytest.approx(30e-9)
    assert btrace.op_seconds(tr, ("copy",)) == 0


def test_top_ops_average_over_devices(tr):
    top = dict(btrace.top_ops(tr))
    assert top["fusion"] == pytest.approx(525e-9)
    assert top["sfs_sweep"] == pytest.approx(50e-9)
    assert "copy" not in top
    assert btrace.op_family("all-gather-start.2.1") == "all-gather-start"


def test_idle_gaps_labelled_by_innermost_host_span(tr):
    assert btrace.idle_gaps(tr)[0] == [(0, 100), (220, 300), (310, 500),
                                       (540, 1000)]
    assert btrace.idle_gaps(tr)[1] == []
    idle = btrace.idle_by_host_span(tr)
    assert [n for n, _ in idle] == ["bench.send", "bench.wait",
                                    "bench.dispatch"]
    assert dict(idle) == pytest.approx({"bench.send": 230e-9,
                                        "bench.wait": 135e-9,
                                        "bench.dispatch": 50e-9})


def test_save_and_load_round_trip(tr, tmp_path):
    path = str(tmp_path / "t.json.gz")
    btrace.save(tr, path)
    assert btrace.load_json(path) == tr


def test_window_span_must_be_unique(tr):
    tr["host"].append(["bench.window", 5, 5])
    with pytest.raises(RuntimeError):
        btrace.window(tr)


def test_op_name_of_tpu_hlo_text():
    text = ('%sfs_sweep.2 = (f32[64,16384]{1,0:T(8,128)S(1)}) '
            'custom-call(f32[64,256256]{1,0} %pad_bitcast_fusion)')
    assert btrace.op_name(text) == "sfs_sweep.2"
    assert btrace.op_name("fusion.3") == "fusion.3"
    assert btrace.CHIP_PLANE.fullmatch("/device:TPU:3")
    assert not btrace.CHIP_PLANE.fullmatch("/device:CUSTOM:Megascale Trace")


def test_recorded_tpu_trace_of_two_queries():
    """Two HOU-shape one-shot queries recorded on one v5e chip."""
    tr = btrace.load_json(os.path.join(os.path.dirname(FIXTURE),
                                       "trace_hou7_tpu.json.gz"))
    lo, hi = btrace.window(tr)
    (busy,) = btrace.busy(tr)
    assert 0 < busy <= (hi - lo) * 1e-9
    sweep = btrace.op_seconds(tr, ("sfs_sweep",))
    mask = btrace.op_seconds(tr, ("dominated_mask",))
    assert 0 < mask < sweep < busy
    families = [n for n, _ in btrace.top_ops(tr)]
    assert "sfs_sweep" in families and "dominated_mask" in families
    idle = btrace.idle_by_host_span(tr)
    assert {n for n, _ in idle} <= {"bench.wait", "bench.dispatch", "none"}
    assert sum(s for _, s in idle) == pytest.approx((hi - lo) * 1e-9 - busy)
