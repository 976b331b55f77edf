"""The trace reduction, on synthetic spans."""

import pytest

import tiny_cells  # noqa: F401  (puts the benchmark on the path)
import trace_reduce as T


def ops(*iv, module="jit_step"):
    return [T.Span(n, a, b, module) for n, a, b in iv]


def test_busy_union_merges_overlaps_and_clips():
    o = ops(("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 3.0, 4.0))
    assert T.union(o) == [(0.0, 1.5), (3.0, 4.0)]
    assert T.busy_seconds(o, 0.0, 5.0) == pytest.approx(2.5)
    assert T.busy_seconds(o, 1.0, 3.5) == pytest.approx(1.0)


def test_matching_by_name_and_module():
    o = (ops(("spm_fwd.1", 0, 1), ("fusion.2", 1, 2), module="jit_prefill")
         + ops(("spm_bwd.3", 2, 4), module="jit_tick"))
    assert len(T.matching(o, "spm")) == 2
    assert [s.name for s in T.matching(o, "spm", "prefill")] == ["spm_fwd.1"]
    assert T.op_seconds(T.matching(o, "SPM")) == pytest.approx(3.0)


def test_idle_gaps_attributed_to_innermost_host_span():
    o = ops(("x", 0.0, 1.0), ("y", 2.0, 3.0), ("z", 3.5, 4.0))
    host = [T.Span("bench.window", 0.0, 5.0), T.Span("bench.step", 0.0, 3.2),
            T.Span("bench.device_get", 1.0, 2.0),
            T.Span("bench.loader", 3.2, 3.6)]
    gaps = dict(T.idle_gaps(o, host, 0.0, 5.0))
    assert gaps["bench.device_get"] == pytest.approx(1.0)
    assert gaps["bench.loader"] == pytest.approx(0.5)
    assert gaps["none"] == pytest.approx(1.0)
    assert sum(gaps.values()) == pytest.approx(5.0 - 2.5)


def test_op_name_drops_the_instruction_text():
    full = ("%fusion.3 = bf16[1024,5120]{1,0} fusion(bf16[1024,5120] "
            "%spm_stack_kernel_call.91), kind=kLoop")
    assert T.op_name(full) == "fusion.3"
    assert not T.matching([T.Span(T.op_name(full), 0, 1)], "spm")
    assert T.op_name("%spm_stack_kernel_call.91 = bf16[8] custom-call()") \
        == "spm_stack_kernel_call.91"
    assert T.op_name("fusion.2") == "fusion.2"


def test_top_ops_fold_numeric_suffixes():
    o = ops(("fusion.1", 0, 1), ("fusion.22", 1, 3), ("spm", 3, 3.5))
    assert T.top_ops(o) == [("fusion", 3.0), ("spm", 0.5)]


def test_modules_attached_by_interval():
    o = [T.Span("op", 1.0, 1.5), T.Span("op", 5.0, 5.5)]
    mods = [T.Span("jit_prefill", 0.5, 2.0), T.Span("jit_tick", 4.0, 6.0)]
    assert [s.module for s in T._attach_modules(o, mods)] == [
        "jit_prefill", "jit_tick"]


def test_window_is_the_bench_window_span():
    tr = T.Trace({"/device:TPU:0": []},
                 [T.Span("bench.window", 2.0, 7.0), T.Span("bench.step", 3, 4)])
    assert tr.window() == (2.0, 7.0)


def _host_only_trace(d):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(jnp.arange(8.0) * 2)
    jax.profiler.stop_trace()


def test_trace_without_device_plane_is_refused(tmp_path):
    _host_only_trace(str(tmp_path))
    with pytest.raises(RuntimeError, match="no accelerator plane"):
        T.load(str(tmp_path))
    tr = T.load(str(tmp_path), cpu_stand_in=True)
    assert tr.stand_in and list(tr.device_ops) == ["/host:CPU"]


@pytest.mark.parametrize("metric, silent_ok", [
    ("spm_roofline.train", True), ("attn_roofline.train", True),
    ("attn_share.train", False), ("spm_share.train", False)])
def test_a_reader_that_reads_nothing_on_a_device_trace(tmp_path, metric,
                                                      silent_ok):
    """On a device trace, only a kernel's roofline may read nothing (its
    kernel left the path); any other silent reader fails the run."""
    import dataclasses

    import harness
    import spec
    cell = spec.load_cell("tiny.train", root=tiny_cells.make(str(tmp_path)))
    cell = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer if m["name"] == metric])
    tr = T.Trace({"/device:TPU:0": ops(("fusion.1", 0.0, 1.0))},
                 [T.Span("bench.window", 0.0, 2.0)])
    host = {"device_kind": "TPU v5 lite", "steps_traced": 1, "batch": 2,
            "seq": 32}
    assert harness.may_be_silent(metric) == silent_ok
    if silent_ok:
        assert harness.read_per_layer(cell, host, tr, cell.kind) == {}
    else:
        with pytest.raises(RuntimeError, match=metric):
            harness.read_per_layer(cell, host, tr, cell.kind)
    stand_in = dataclasses.replace(tr, stand_in=True)
    assert harness.read_per_layer(cell, host, stand_in, cell.kind) == {}
