"""A throwaway checkout with small cells, for tests on the CPU.

``make(tmp)`` copies the benchmark beside a ``BENCHMARK.json`` of its own
with a small Qwen3 configuration (widths of a few dozen) under a training
and a serving mix, and generous limits, so a whole run fits a CPU test.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "model_type": "qwen3", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "tie_word_embeddings": True, "rope_theta": 1000000,
    "rms_norm_eps": 1e-06,
    "program": {"arch": "qwen3-1.7b", "overrides": {
        "d_model": 64, "d_ff": 96, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
        "q_chunk": 16, "k_chunk": 16},
        "require": {"qk_norm": True}},
    "reference": "qwen3_reference.py",
}

# a mixture-of-experts configuration at the widths of the zoo's qwen3-moe
# SMOKE, keyed as Qwen3-30B-A3B's config.json: its expert keys are checked
# as stated; every FFN is sparse, so the published intermediate_size has
# no program field
TINY_MOE = {
    "model_type": "qwen3_moe", "hidden_size": 64, "intermediate_size": 192,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "tie_word_embeddings": False, "rope_theta": 1000000,
    "rms_norm_eps": 1e-06,
    "program": {"arch": "qwen3-moe-30b-a3b", "overrides": {
        "d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "vocab_size": 256, "n_experts": 8, "top_k": 2,
        "moe_d_ff": 32, "tie_embeddings": False,
        "q_chunk": 16, "k_chunk": 16},
        "unmapped": {
            "intermediate_size": "every layer's FFN is a mixture of "
                                 "experts; no dense FFN is held",
            "norm_topk_prob": "the program's gating always renormalises "
                              "over the top k; the reference follows the "
                              "published flag, and the check compares"},
        "require": {"qk_norm": True}},
}

TRAIN = {"kind": "train", "batch": 2, "seq": 32,
         "optimizer": {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                       "weight_decay": 0.01, "clip_norm": 1.0,
                       "warmup_steps": 100, "total_steps": 10000,
                       "min_lr_frac": 0.1},
         "check_steps": 3, "trace_after_steps": 1, "trace_steps": 1}

SERVE = {"kind": "serve", "slots": 4, "max_len": 72, "requests": 24,
         "block": 8, "schedule_seed": 0,
         "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 56},
         "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
         "greedy_share": 0.5,
         "sampled": {"temperature": 0.8, "top_k": 40, "top_p": 1.0},
         "arrivals": {"rate_per_tick": 0.4},
         "check_requests": 6, "drain_limit_s": 60,
         "trace_after_s": 0.0, "trace_s": 0.5}

LIMITS = {"train": {"loss_gap": {"limit": 1e-3}, "grad_gap": {"limit": 0.05},
                    "change_gap": {"limit": 0.05}},
          "serve": {"logit_gap": {"limit": 0.1},
                    "first_logit_err": {"limit": 0.2}}}


def make(tmp: str, configs=None) -> str:
    """A checkout at ``tmp`` with cells ``tiny.train`` and ``tiny.serve``;
    returns its root."""
    shutil.copytree(BENCH, os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = os.path.join(tmp, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(configs or TINY, f)
    for name, mix in (("tiny-train", TRAIN), ("tiny-serve", SERVE)):
        with open(os.path.join(b, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    for kind, lim in LIMITS.items():
        with open(os.path.join(b, "limits", f"tiny.{kind}.json"), "w") as f:
            json.dump(lim, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.train" if "train" in m["name"]
                              else "tiny.serve"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def run(root: str, workload: str, seed: int = 7, seconds: float = 1.0,
        trace: int = 0, faults=None) -> dict:
    """A whole run of a cell without the chip check (the host's XLA ops
    stand in for the device trace)."""
    import time

    import harness
    import spec
    cell = spec.load_cell(workload, root=root)
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
    return harness.run(cell, args, t_start=time.time(), faults=faults,
                       cpu_stand_in=True)
