"""Whole serving runs of a small cell on the CPU (the chip check
skipped): a sound run is correct, a served token altered where it is
produced is not, and the control (the reference in fp8 picking the
tokens) fails the limit."""

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
    r = tiny_cells.run(root, "tiny.serve", trace=1, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    m = r["metrics"]
    assert m["decode_tick_ms.serve"]["value"] > 0
    assert m["prefill_ms_per_ktok.serve"]["value"] > 0
    assert r["device"]["busy_s"] > 0


def test_untraced_run_reports_end_to_end(root):
    r = tiny_cells.run(root, "tiny.serve", seconds=0.5)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_s", "itl_p95_ms",
                                 "serve_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root):
    r = tiny_cells.run(root, "tiny.serve", faults={"alter_token": True})
    assert not r["correct"], r["checks"]


def test_control_fails_the_limit(root):
    import checks
    import harness
    import spec
    cell = spec.load_cell("tiny.serve", root=root)
    drv = spec.kind_module(cell)
    srv = drv.Server(cell, 9, harness.span)
    w = srv.window(60.0)
    rd = drv.reference_readings(cell, w["sample"], w["served"],
                                w["first_logits"], ("f32", "fp8"),
                                params=srv.params)
    assert checks.judge(drv.numbers_of(rd["f32"]), cell.limits)[0]
    assert not checks.judge(drv.numbers_of(rd["fp8"]), cell.limits)[0]
