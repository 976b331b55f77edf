"""§Perf I5: fused-Pallas-kernel projection of the memory roofline term.

The dry-run lowers the SPM composition as separate XLA stage ops: every
stage is ≥1 HBM read + 1 write of the full activation (L+1 round-trips
per SPM linear).  The Pallas kernel (kernels/spm_stack.py, validated in
interpret mode) keeps the tile in VMEM across all fused stages: 1 read +
1 write per run boundary (kernels/ops.plan_runs).  This script computes
both traffic models analytically per cell and projects the memory term
with SPM traffic replaced by the fused model — the number a real-TPU run
would see.

Projection = measured_bytes − unfused_spm_bytes(analytic)
             + fused_spm_bytes(analytic), floored at fused-only traffic.
"""

from __future__ import annotations

import argparse
import json
import os

from repro.configs import SHAPES, get_config
from repro.core.linear import LinearConfig
from repro.core.pairings import default_n_stages
from repro.kernels.ops import plan_runs
from repro.launch.hlo_analysis import V5E, peaks, roofline_terms

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")

DTYPE_B = 2   # bf16 activations


def spm_linear_sites(cfg):
    """(n, L, calls-per-layer-stack) for every SPM linear site."""
    sites = []

    def lin(d_in, d_out, count=1):
        n = max(d_in, d_out)
        n += n % 2
        L = cfg.spm_stages or default_n_stages(n)
        sites.append((n, L, count))

    H, Hkv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    for spec in cfg.layers:
        if spec.mixer == "attn":
            lin(d, H * dh)
            lin(d, Hkv * dh)
            lin(d, Hkv * dh)
            lin(H * dh, d)
        else:  # mamba
            d_inner = 2 * d
            lin(d, 2 * d_inner + 2 * cfg.ssm_state + d_inner // cfg.ssm_head)
            lin(d_inner, d)
        if spec.mlp == "dense":
            lin(d, cfg.d_ff)
            lin(d, cfg.d_ff)
            lin(cfg.d_ff, d)
        elif spec.mlp == "moe":
            # routed tokens ≈ top_k/n_experts of batch hit each expert; in
            # aggregate every token passes through top_k experts:
            frac = cfg.top_k
            lin(d, cfg.moe_d_ff, count=frac)
            lin(d, cfg.moe_d_ff, count=frac)
            lin(cfg.moe_d_ff, d, count=frac)
            if cfg.shared_d_ff:
                lin(d, cfg.shared_d_ff)
                lin(d, cfg.shared_d_ff)
                lin(cfg.shared_d_ff, d)
        if spec.shared_block:
            lin(d, H * dh)
            lin(d, Hkv * dh)
            lin(d, Hkv * dh)
            lin(H * dh, d)
            lin(d, cfg.shared_attn_d_ff)
            lin(d, cfg.shared_attn_d_ff)
            lin(cfg.shared_attn_d_ff, d)
    return sites


def spm_traffic(cfg, tokens_local: int, passes: float = 3.0):
    """(unfused_bytes, fused_bytes) per chip per step.

    passes: fwd + remat-recompute + bwd ≈ 3 activation passes.
    Unfused: each of L stages reads+writes the (tokens, n) activation.
    Fused:   1 read + 1 write per kernel run (plan_runs boundaries).
    """
    unfused = fused = 0.0
    for n, L, count in spm_linear_sites(cfg):
        act = tokens_local * n * DTYPE_B
        runs = plan_runs(n if n % 2 == 0 else n + 1,
                         tuple([1] * L))  # stride values don't matter for
        # run count at tile cap; real schedules give same-or-fewer runs
        n_runs = len(runs)
        unfused += count * passes * L * 2 * act
        fused += count * passes * n_runs * 2 * act
    return unfused, fused


def project(arch: str, shape_name: str, profile_file: str):
    fp = os.path.join(RESULTS, "single", profile_file)
    with open(fp) as f:
        rec = json.load(f)
    assert rec["ok"], rec.get("error")
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_chips = rec["n_chips"]
    if shape.kind == "train":
        tokens_local = shape.global_batch * shape.seq_len // n_chips
    elif shape.kind == "prefill":
        tokens_local = shape.global_batch * shape.seq_len // n_chips
    else:
        tokens_local = max(shape.global_batch // n_chips, 1)
    passes = 3.0 if shape.kind == "train" else 1.0
    unfused, fused = spm_traffic(cfg, tokens_local, passes)
    measured = rec["cost"]["bytes_accessed"]
    projected = max(measured - unfused + fused, fused)
    terms_now = rec["roofline"]
    terms_proj = roofline_terms(rec["cost"]["flops"], projected,
                                rec["collectives"]["total"], peaks(V5E))
    return {
        "cell": f"{arch} x {shape_name}",
        "measured_bytes": measured,
        "unfused_spm_bytes": unfused,
        "fused_spm_bytes": fused,
        "projected_bytes": projected,
        "memory_s_now": terms_now["memory_s"],
        "memory_s_projected": terms_proj["memory_s"],
        "dominant_projected": terms_proj["dominant"],
        "roofline_frac_projected": terms_proj["roofline_fraction"],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    args = ap.parse_args(argv)
    cells = [
        ("qwen3-1.7b", "train_4k", "qwen3-1.7b__train_4k__spm_dp_g.json"),
        ("zamba2-1.2b", "train_4k", "zamba2-1.2b__train_4k__spm_dp_g.json"),
        ("qwen3-moe-30b-a3b", "decode_32k",
         "qwen3-moe-30b-a3b__decode_32k__spm_dp_g.json"),
    ]
    print("# I5 fused-kernel projection (Pallas VMEM stage fusion)")
    for arch, shape, f in cells:
        try:
            r = project(arch, shape, f)
        except FileNotFoundError:
            print(f"{arch} x {shape}: (optimized dry-run record missing)")
            continue
        print(f"\n{r['cell']}:")
        for k in ("measured_bytes", "unfused_spm_bytes", "fused_spm_bytes",
                  "projected_bytes"):
            print(f"  {k:22s} {r[k]:.3e}")
        print(f"  memory term {r['memory_s_now']*1e3:.1f} ms -> "
              f"{r['memory_s_projected']*1e3:.1f} ms projected; dominant "
              f"-> {r['dominant_projected']}, roofline frac "
              f"{r['roofline_frac_projected']:.1%}")


if __name__ == "__main__":
    main()
