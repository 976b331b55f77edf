"""The mixture-of-experts cell and the decode cell in the real
``BENCHMARK.json``, the three readers of the expert cell on stand-in
spans, and whole runs of a small expert cell on the CPU against the
plain reference ``qwen3_moe_reference.py``."""

import copy
import importlib
import json
import os
import types

import pytest

import tiny_cells

peaks = importlib.import_module("peaks")
spec = importlib.import_module("spec")
T = importlib.import_module("trace_reduce")
work = importlib.import_module("work")

MOE = "qwen3-moe-30b-a3b.train-8k"
DECODE = "qwen3-1.7b.serve-decode"
READERS = ("moe_share.train", "moe_spm_roofline.train", "moe_mfu.train")


def _reader(name):
    return spec.load_module(os.path.join(spec.BENCH, "metrics",
                                         f"{name}.py"))


def test_real_benchmark_loads_both_cells():
    moe, dec = spec.load_cell(MOE), spec.load_cell(DECODE)
    assert (moe.kind, moe.chips, dec.kind, dec.chips) == ("train", 1,
                                                           "serve", 1)
    cfg = spec.program_config(moe)
    assert (cfg.n_layers, cfg.vocab_size, cfg.n_experts, cfg.top_k,
            cfg.moe_d_ff, cfg.d_ff) == (12, 37984, 128, 8, 768, 0)
    assert (cfg.moe_aux_coef, cfg.moe_aux) == (0.001, "hf")
    assert not cfg.tie_embeddings
    assert spec.program_config(dec).name == "qwen3-1.7b"
    assert {m["name"] for m in moe.end_to_end} == {"train_tokens_per_s",
                                                   "setup_s"}
    assert {m["name"] for m in dec.end_to_end} == {
        "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(READERS) <= {m["name"] for m in moe.per_layer}
    names = {m["name"] for m in moe.per_layer}
    # these two count a dense intermediate_size-wide FFN
    assert not names & {"mfu.train", "spm_roofline.train"}
    assert set(moe.limits) == set(spec.load_cell(
        "qwen3-1.7b.train-4k").limits)
    assert set(dec.limits) == set(spec.load_cell(
        "qwen3-32b.serve-prefill").limits)


def _ctx(cell, ops, steps=2, tokens_per_s=1000.0):
    return types.SimpleNamespace(
        host={"steps_traced": steps, "batch": cell.traffic["batch"],
              "seq": cell.traffic["seq"], "tokens_per_s": tokens_per_s},
        shape=spec.published(cell), peaks=peaks.peaks("TPU v5 lite"),
        ops=[ops], window=(0.0, 4.0), work=work, tr=T)


def test_readers_on_stand_in_spans():
    cell = spec.load_cell(MOE)
    ops = [T.Span("gather.3", 0.0, 0.5, "jit_step", scope="moe"),
           T.Span("spm_stack_kernel_call.1", 0.5, 2.5, "jit_step",
                  scope="spm"),
           T.Span("spm_stack_bwd_kernel_call.2", 2.5, 3.5, "jit_step",
                  scope="spm"),
           T.Span("fusion.9", 3.5, 4.0, "jit_step", scope="attn")]
    ctx = _ctx(cell, ops)
    assert _reader("moe_share.train").read(ctx) == pytest.approx(12.5)
    shape, pk = ctx.shape, ctx.peaks
    # by hand: q and o over 4096 lanes in 12 stages, k and v over 2048
    # in 11, each expert map over 2048 in 11 at the routed rows
    rows, routed = 8192, 8192 * 8
    w = _reader("moe_spm_roofline.train").spm_work(work, shape, rows)
    pbytes = (11 * 1024 * 4 + 2 * 2048) * 4
    fl = lambda r, n, L, back: r * n * ((7 * L + 4) if back
                                        else (3 * L + 2))
    flops = 12 * sum(2 * fl(rows, 4096, 12, b) + 2 * fl(rows, 2048, 11, b)
                     + 3 * fl(routed, 2048, 11, b) for b in (False, True))
    assert w["flops"] == pytest.approx(flops)
    expert_bytes = 12 * 3 * 127 * 3 * pbytes      # other experts' params
    assert w["bytes"] > expert_bytes
    least = 2 * max(w["flops"] / pk["peak_flops"], w["bytes"] / pk["hbm_bw"])
    assert _reader("moe_spm_roofline.train").read(ctx) == pytest.approx(
        100 * least / 3.0)
    per_token = _reader("moe_mfu.train").step_flops(work, shape, 1, 8192) \
        / 8192
    router = 3 * 2 * 2048 * 128 * 12
    assert per_token > router + flops / 8192
    assert _reader("moe_mfu.train").read(ctx) == pytest.approx(
        100 * per_token * 1000.0 / pk["peak_flops"])
    assert _reader("moe_spm_roofline.train").read(_ctx(cell, ops[:1])) \
        is None
    assert _reader("moe_share.train").read(_ctx(cell, ops[1:])) is None


# ---------------------------------------------------------------------------
# a small expert cell, run whole on the CPU
# ---------------------------------------------------------------------------

def _tiny_moe():
    conf = copy.deepcopy(tiny_cells.TINY_MOE)
    conf["reference"] = "qwen3_moe_reference.py"
    conf["router_aux_loss_coef"] = 0.001
    conf["program"]["require"].update(moe_aux_coef=0.001, moe_aux="hf")
    return conf


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny_cells.make(str(tmp_path_factory.mktemp("bench")))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-moe.json"), "w") as f:
        json.dump(_tiny_moe(), f)
    with open(os.path.join(b, "traffic", "tiny-moe-train.json"), "w") as f:
        json.dump(tiny_cells.TRAIN, f)
    with open(os.path.join(b, "limits", "tiny-moe.train.json"), "w") as f:
        json.dump(tiny_cells.LIMITS["train"], f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-moe.train", "config": "tiny-moe",
                               "traffic": "tiny-moe-train", "chips": 1,
                               "why": "test"})
    # the small expert cell stands in for the real one in metric lists
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    lists = {m["name"]: m.get("workloads", [])
             for m in real["end_to_end"] + real["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if MOE in lists[m["name"]]:
            m["workloads"] = [w for w in m["workloads"]
                              if m["name"] not in READERS] \
                + ["tiny-moe.train"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"peak_flops": 1e12,
                                             "hbm_bw": 1e11,
                                             "hbm_bytes": 1e10})


def test_traced_expert_run_is_correct(root, cpu_peaks):
    r = tiny_cells.run(root, "tiny-moe.train", trace=1)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 1 and r["failed"] == 0
    # the host's ops that stand in for a device trace carry no scope and
    # no kernel names: of the new readers only the host-clock one reads
    m = r["metrics"]
    assert 0 < m["moe_mfu.train"]["value"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_expert_step_is_not_correct(root, fault):
    r = tiny_cells.run(root, "tiny-moe.train", faults={fault: True})
    assert not r["correct"], r["checks"]
