"""Whole training runs of a small cell on the CPU (the chip check
skipped): a sound run is correct; a step that returns its state unchanged
and a step over half its batch are not; the control (the reference in
fp8 in the program's place) fails the limits."""

import pytest

import tiny_cells


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cells.make(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def cpu_peaks(monkeypatch):
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"peak_flops": 1e12,
                                             "hbm_bw": 1e11,
                                             "hbm_bytes": 1e10})


def test_traced_run_is_correct_and_reads_layers(root, cpu_peaks):
    r = tiny_cells.run(root, "tiny.train", trace=1)
    assert r["correct"], r["checks"]
    assert list(r["checks"])[-1] == "compiles_in_window"
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["attempted"] > 1 and r["failed"] == 0
    m = r["metrics"]
    assert 0 < m["mfu.train"]["value"]
    assert 0 <= m["device_idle.train"]["value"] < 100
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] > 0
    assert r["breakdown"]["device_ops"]
    assert list(r) [-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_step_is_not_correct(root, fault):
    r = tiny_cells.run(root, "tiny.train", faults={fault: True})
    assert not r["correct"], r["checks"]


def test_control_fails_the_limits(root):
    import checks
    import spec
    cell = spec.load_cell("tiny.train", root=root)
    drv = spec.kind_module(cell)
    ref = drv.reference_readings(cell, 5)
    ctl = drv.reference_readings(cell, 5, "fp8")
    ok, _ = checks.judge(drv.compare(ctl, ref)[0], cell.limits)
    assert not ok


def test_half_batch_fault_halves_rows_or_positions():
    import numpy as np
    import spec
    cell = spec.load_cell("qwen3-1.7b.train-4k")
    drv = spec.kind_module(cell)
    assert drv.halve(np.zeros((2, 8))).shape == (1, 8)
    assert drv.halve(np.zeros((1, 8))).shape == (1, 4)
