"""The attention kernel's roofline share, read from synthetic spans."""

import importlib
import os
import types

import pytest

import tiny_cells  # noqa: F401  (puts the benchmark on the path)

peaks = importlib.import_module("peaks")
spec = importlib.import_module("spec")
T = importlib.import_module("trace_reduce")
work = importlib.import_module("work")

QWEN3_1_7B = {"hidden_size": 2048, "num_hidden_layers": 28,
              "num_attention_heads": 16, "num_key_value_heads": 8,
              "head_dim": 128}
READ = spec.load_module(os.path.join(spec.BENCH, "metrics",
                                     "attn_roofline.train.py")).read


def _ctx(names_and_seconds, steps=2):
    ops, t = [], 0.0
    for name, dur in names_and_seconds:
        ops.append(T.Span(name, t, t + dur, "jit_step"))
        t += dur
    return types.SimpleNamespace(
        host={"steps_traced": steps, "batch": 1, "seq": 4096},
        shape=QWEN3_1_7B, peaks=peaks.peaks("TPU v5 lite"), ops=[ops],
        work=work, tr=T)


def test_share_counts_only_the_attention_kernels():
    least = 2 * 3 * work.attention_flops(QWEN3_1_7B, 4096) \
        / peaks.peaks("TPU v5 lite")["peak_flops"]
    ctx = _ctx([("splash_mqa_fwd_residuals.3", 0.1),
                ("splash_mqa_dq_no_residuals.1", 0.1),
                ("splash_mqa_dkv_no_residuals.2", 0.2),
                ("spm_stack_kernel_call.7", 5.0), ("fusion.4", 1.0)])
    assert READ(ctx) == pytest.approx(100.0 * least / 0.4)


def test_no_attention_kernel_reads_none():
    ctx = _ctx([("bitcast_dynamic-update-slice_fusion.2", 0.5),
                ("constant_dynamic-slice_fusion", 0.4), ("copy.3", 0.4),
                ("spm_stack_kernel_call.7", 5.0)])
    assert READ(ctx) is None
    assert READ(_ctx([("splash_mqa_fwd_residuals", 0.1)], steps=0)) is None
