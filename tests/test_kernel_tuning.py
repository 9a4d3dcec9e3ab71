"""Kernel autotuner: table persistence, env/default resolution, the
calibration pass's bitwise gate, and the engine's 'auto' consultation
rules (explicit impl / pinned wtile always win)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.parallel import SkyConfig
from repro.kernels.tuning import (TuneEntry, TuningTable, calibrate_kernels,
                                  default_table, set_default_table,
                                  tuning_key)
from repro.serve.engine import SkylineEngine, SkylineRequest


@pytest.fixture(autouse=True)
def _clean_default_table():
    set_default_table(None)
    yield
    set_default_table(None)


def _table(block=128, wtile=128, ok=True, platform=None):
    return TuningTable(entries={
        tuning_key("sweep", 4, jnp.float32):
            TuneEntry(block=block, wtile=wtile, time_us=1.0, impl="jnp",
                      bitwise_ok=ok)},
        topology={"platform": platform or jax.default_backend()})


def test_table_json_roundtrip(tmp_path):
    t = _table()
    path = t.save(str(tmp_path / "sub" / "tuning.json"))
    t2 = TuningTable.load(path)
    assert t2.to_json() == t.to_json()
    assert t2.lookup("sweep", 4, jnp.float32).block == 128
    assert t2.lookup("sweep", 7, jnp.float32) is None
    assert len(t2) == 1


def test_env_var_loads_default_table(tmp_path, monkeypatch):
    path = _table(block=64, wtile=64).save(str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_KERNEL_TUNING", path)
    set_default_table(None)  # re-arm the lazy load
    tab = default_table()
    assert tab is not None and tab.lookup("sweep", 4, "float32").block == 64
    # no variable, no table
    monkeypatch.delenv("REPRO_KERNEL_TUNING")
    set_default_table(None)
    assert default_table() is None


def test_unreadable_table_path_raises(tmp_path, monkeypatch):
    """A named table that cannot be read fails loudly: the run asked for
    a tuned geometry and must not silently run another one."""
    monkeypatch.setenv("REPRO_KERNEL_TUNING", str(tmp_path / "nope.json"))
    with pytest.raises(ValueError, match="cannot be read"):
        default_table()
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("REPRO_KERNEL_TUNING", str(bad))
    with pytest.raises(ValueError, match="cannot be read"):
        default_table()


def test_table_from_another_platform_refused(tmp_path, monkeypatch):
    """A geometry timed on one backend is never applied on another (a
    CPU-timed table says nothing about the TPU)."""
    other = "tpu" if jax.default_backend() != "tpu" else "cpu"
    path = _table(platform=other).save(str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_KERNEL_TUNING", path)
    with pytest.raises(ValueError, match="measured on platform"):
        default_table()
    with pytest.raises(ValueError, match="measured on platform"):
        set_default_table(TuningTable.load(path))


def test_calibrate_kernels_quick():
    rep = calibrate_kernels(None, ds=(4,), n=512, p=2, capacity=256,
                            blocks=(64, 128), repeat=1, apply=True)
    table = rep["table"]
    assert len(table) >= 1 and rep["divergent"] == []
    entry = table.lookup("sweep", 4, jnp.float32)
    assert entry is not None and entry.bitwise_ok
    # the winner is the argmin of the measured (verified) candidates
    times = rep["keys"][tuning_key("sweep", 4, jnp.float32)]["times_us"]
    assert times[f"b{entry.block}/t{entry.wtile}"] == min(times.values())
    # apply=True with engine=None installs the process default
    assert default_table() is table


def test_engine_consults_table_only_for_auto():
    eng = SkylineEngine(SkyConfig())
    eng.kernel_tuning = _table()
    tuned = eng._cfg_for(None, 4, "float32")
    assert (tuned.block, tuned.wtile) == (128, 128)
    # value-equal configs share the compile-cache key
    assert tuned == dataclasses.replace(eng.cfg, block=128, wtile=128)
    # no entry for this (d, dtype) -> untouched config
    assert eng._cfg_for(None, 7, "float32") == eng.cfg
    # an explicit per-request impl bypasses tuning entirely
    assert eng._cfg_for("perpair", 4, "float32").wtile == 0
    # a non-'auto' engine impl is never overridden
    eng_jnp = SkylineEngine(SkyConfig(impl="jnp"))
    eng_jnp.kernel_tuning = _table()
    assert eng_jnp._cfg_for(None, 4, "float32") == eng_jnp.cfg
    # an explicitly pinned wtile wins over the table
    eng_pin = SkylineEngine(SkyConfig(wtile=64))
    eng_pin.kernel_tuning = _table()
    assert eng_pin._cfg_for(None, 4, "float32").wtile == 64
    # a divergent entry is never applied
    eng_bad = SkylineEngine(SkyConfig())
    eng_bad.kernel_tuning = _table(ok=False)
    assert eng_bad._cfg_for(None, 4, "float32") == eng_bad.cfg


def test_tuned_engine_answers_bitwise_identical():
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.integers(0, 9, (700, 4)) / 9, jnp.float32)
    req = [SkylineRequest(data=pts)]
    eng = SkylineEngine(SkyConfig())
    eng.kernel_tuning = _table()
    plain = SkylineEngine(SkyConfig())
    (bt, _), (bp, _) = eng.submit_many(req)[0], plain.submit_many(req)[0]
    np.testing.assert_array_equal(np.asarray(bt.points),
                                  np.asarray(bp.points))
    np.testing.assert_array_equal(np.asarray(bt.mask), np.asarray(bp.mask))
    assert int(bt.count) == int(bp.count)
