"""Seeded generators: a seed gives the same requests, arrivals and
prefill shapes; every seed the same schedule, with its own token ids; a
mix's schedule seed the same sizes in another order; set-up warms
exactly the shapes the window uses."""

import json
import os

import numpy as np
import pytest

import tiny_cells
import traffic

MIX = tiny_cells.SERVE


def test_same_seed_same_requests_and_arrivals():
    a, ta = traffic.serve_requests(MIX, 256, 2**31 + 77)
    b, tb = traffic.serve_requests(MIX, 256, 2**31 + 77)
    assert ta == tb
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.temperature, x.top_k) == (
            y.max_new_tokens, y.temperature, y.top_k)


def test_every_seed_gets_the_same_sizes_in_another_order():
    """Every schedule seed of a mix: the same sizes, flags and gaps."""
    a, ta = traffic.serve_requests(MIX, 256, 1)
    b, tb = traffic.serve_requests(dict(MIX, schedule_seed=5), 256, 1)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens
                                                         for r in b)
    assert sum(r.greedy for r in a) == sum(r.greedy for r in b)
    gaps = lambda t: sorted(np.diff([0] + t))
    assert ta != tb
    assert abs(sum(gaps(ta)) - sum(gaps(tb))) <= len(ta)


def test_run_seed_draws_the_tokens_and_not_the_schedule():
    a, ta = traffic.serve_requests(MIX, 256, 1)
    b, tb = traffic.serve_requests(MIX, 256, 2**31 + 3)
    assert ta == tb
    assert [(len(r.prompt), r.max_new_tokens, r.temperature) for r in a] \
        == [(len(r.prompt), r.max_new_tokens, r.temperature) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_each_block_holds_the_whole_distribution():
    vals = traffic.stratified(np.random.default_rng(0), 20,
                              lambda u: np.round(10 * u), block=5)
    blocks = vals.reshape(4, 5)
    assert all(sorted(b) == [1, 3, 5, 7, 9] for b in blocks)
    assert len({tuple(b) for b in blocks}) > 1


def test_seed32_separates_large_seeds():
    assert traffic.seed32(5) != traffic.seed32(2**33 + 5)
    assert 0 <= traffic.seed32(2**40) < 2**31


def test_arrivals_follow_the_exponential_gap_rule():
    rate, n = 0.25, 4000
    ticks = traffic.stratified_arrivals(np.random.default_rng(3), n, rate)
    assert ticks == sorted(ticks)
    gaps = np.diff([0] + ticks)
    assert np.mean(gaps) == pytest.approx(1 / rate, rel=0.02)
    # exponential: half the gaps are under ln 2 over the rate
    assert np.mean(gaps < np.log(2) / rate) == pytest.approx(0.5, abs=0.06)


def test_knee_sweep_is_recorded_in_the_serving_mix():
    with open(os.path.join(tiny_cells.BENCH, "traffic",
                           "serve-prefill.json")) as f:
        mix = json.load(f)
    arr = mix["arrivals"]
    assert arr["knee_per_tick"] and arr["sweep"]
    assert arr["rate_per_tick"] == pytest.approx(
        arr["load"] * arr["knee_per_tick"], abs=5e-3)


def test_setup_warms_exactly_the_windows_prefill_shapes(tmp_path):
    import harness
    import spec
    root = tiny_cells.make(str(tmp_path))
    cell = spec.load_cell("tiny.serve", root=root)
    drv = spec.kind_module(cell)
    srv = drv.Server(cell, 11, harness.span)
    warmed = set(srv.probe.shapes)
    srv.probe.shapes = set()
    w = srv.window(60.0)
    assert w["complete"]
    assert srv.probe.shapes == warmed
    first = [(p[2], p[3]) for p in srv.probe.prefills]
    assert set(first) == warmed
    srv.window(60.0)
    assert [(p[2], p[3]) for p in srv.probe.prefills] == first
