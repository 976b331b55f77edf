"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build the production mesh (16x16 single pod / 2x16x16 multi-pod),
  * construct abstract state/batch/cache (ShapeDtypeStruct, no alloc),
  * jit the cell's step function with explicit in/out shardings,
  * ``.lower().compile()`` — success proves the distribution config is
    coherent (sharding match, no OOM-at-compile, collectives supported),
  * record memory_analysis / cost_analysis / collective bytes for
    EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --all --linear-impl dense   # baseline
Results land in results/dryrun/<mesh>/<arch>__<shape>[__<impl>].json.
"""

import os

# the 512 virtual host devices must be requested before jax initializes
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, arch_shapes, get_config,
                           with_overrides)
from repro.configs.shapes import ShapeSpec
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import abstract_cache, abstract_state, input_specs
from repro.models import causal_lm as LM
from repro.models import transformer as T
from repro.optim.adamw import OptimizerConfig
from repro.parallel import sharding as SH
from repro.train.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _batch_shardings(mesh, batch_specs, shape: ShapeSpec,
                     profile: str = "tp"):
    dp_base = SH.data_axes(mesh)
    dp = dp_base
    if profile.startswith("spm_dp") and shape.kind != "decode":
        # SPM collapses params to O(nL): the model axis carries BATCH for
        # train/prefill (full-mesh DP); vocab/EP params still use it.
        dp = dp + ("model",)

    def one(path, x):
        name = SH.tree_path_str(path)
        if name == "index":
            return NamedSharding(mesh, P())
        if name == "positions":                 # (3, B, S)
            return NamedSharding(mesh, P(None, dp, None))
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        if shape.kind == "decode" and shape.seq_sharded:
            return NamedSharding(mesh, P(*([None] * x.ndim)))   # B == 1
        if name == "tokens" and profile == "spm_dp_g2":
            # I6: token ids replicated over "model" so the vocab-sharded
            # gather lowers as mask+all-reduce instead of all-gathering
            # the table; embeds are re-pinned to full-mesh DP in-model.
            return NamedSharding(mesh,
                                 P(dp_base, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P(dp, *([None] * (x.ndim - 1))))

    return jax.tree_util.tree_map_with_path(one, batch_specs)


def lower_cell(cfg: T.ModelConfig, shape: ShapeSpec, mesh,
               profile: str = "tp"):
    """Build + lower the cell's step function.  Returns the lowered jit."""
    import contextlib
    from repro.parallel.ctx import activation_sharding

    if profile == "spm_dp" and cfg.input_kind == "tokens":
        cfg = with_overrides(cfg, embed_onehot=True)
    # spm_dp_g: same shardings, gather-lowered lookup (I2 ablation)
    # spm_dp_g2: + tokens replicated over model, embeds constrained (I6)
    act_ctx = (activation_sharding(mesh, shard_heads=False, full_batch=True)
               if profile == "spm_dp_g2" and shape.kind != "decode"
               else contextlib.nullcontext())
    if profile == "spm_feat":
        # feature axis over "model": two_level SPM linears route through the
        # distributed executor (collective_permute cross stages)
        act_ctx = activation_sharding(mesh, shard_heads=False,
                                      shard_feature=True)
    batch = input_specs(cfg, shape)
    batch_sh = _batch_shardings(mesh, batch, shape, profile)

    if shape.kind == "train":
        state = abstract_state(cfg)
        state_sh = {
            "params": SH.param_shardings(mesh, state["params"], profile),
            "opt": {"mu": SH.param_shardings(mesh, state["opt"]["mu"],
                                             profile),
                    "nu": SH.param_shardings(mesh, state["opt"]["nu"],
                                             profile),
                    "count": NamedSharding(mesh, P())},
            "step": NamedSharding(mesh, P()),
        }
        opt_cfg = OptimizerConfig()
        step = make_train_step(lambda p, b: LM.lm_loss(p, b, cfg), opt_cfg)
        metrics_sh = None
        fn = jax.jit(step,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh))
        with act_ctx:
            lowered = fn.lower(state, batch)

    elif shape.kind == "prefill":
        params = abstract_state(cfg)["params"]
        params_sh = SH.param_shardings(mesh, params, profile)

        def prefill_fwd(p, b):
            logits, _, _ = T.forward(
                p, cfg, tokens=b.get("tokens"), embeds=b.get("embeds"),
                positions=b.get("positions"))
            return logits

        fn = jax.jit(prefill_fwd,
                     in_shardings=(params_sh, batch_sh),
                     out_shardings=None)
        with act_ctx:
            lowered = fn.lower(params, batch)

    else:  # decode
        params = abstract_state(cfg)["params"]
        params_sh = SH.param_shardings(mesh, params, profile)
        cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cache_sh = SH.cache_specs(mesh, cache, seq_sharded=shape.seq_sharded)

        def serve_step(p, tok, c, idx):
            return LM.decode_step(p, cfg, tok, c, idx)

        fn = jax.jit(serve_step,
                     in_shardings=(params_sh, batch_sh["tokens"], cache_sh,
                                   batch_sh["index"]),
                     out_shardings=(None, cache_sh))
        lowered = fn.lower(params, batch["tokens"], cache, batch["index"])

    return lowered


def model_flops(cfg: T.ModelConfig, shape: ShapeSpec) -> dict:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (forward-only), N = non-embedding
    active params (MoE counts top_k + shared experts only)."""
    state = abstract_state(cfg)
    total = sum(int(jnp.prod(jnp.array(x.shape)))
                for x in jax.tree.leaves(state["params"]))
    flat = jax.tree_util.tree_flatten_with_path(state["params"])[0]
    embed = sum(int(jnp.prod(jnp.array(x.shape))) for p, x in flat
                if "embed" in SH.tree_path_str(p))
    expert = sum(int(jnp.prod(jnp.array(x.shape))) for p, x in flat
                 if "/experts/" in SH.tree_path_str(p))
    n_nonembed = total - embed
    if cfg.n_experts:
        active_frac = cfg.top_k / cfg.n_experts
        n_active = n_nonembed - expert + int(expert * active_frac)
    else:
        n_active = n_nonembed
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = 2 * n_active * tokens
    else:
        tokens = shape.global_batch          # one new token per sequence
        mf = 2 * n_active * tokens
    return {"params_total": total, "params_active_nonembed": n_active,
            "tokens": tokens, "model_flops": mf}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             linear_impl: str | None = None, save: bool = True,
             profile: str = "tp", remat: bool = True,
             bf16_logits: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if linear_impl:
        cfg = with_overrides(cfg, linear_impl=linear_impl)
    if not remat:
        cfg = with_overrides(cfg, remat=False)
    if bf16_logits:
        cfg = with_overrides(cfg, logits_dtype="bfloat16")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if profile == "spm_feat":
        from repro.configs import with_feature_sharding
        if cfg.linear_impl == "dense":
            cfg = with_overrides(cfg, linear_impl="spm_general")
        cfg = with_feature_sharding(cfg, int(mesh.shape["model"]))
    n_chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "linear_impl": cfg.linear_impl, "n_chips": int(n_chips),
           "profile": profile, "remat": remat}
    t0 = time.time()
    try:
        with mesh:
            lowered = lower_cell(cfg, shape, mesh, profile)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = H.memory_analysis_terms(compiled)
        cost = H.cost_analysis_terms(compiled)
        coll = H.collective_bytes(compiled.as_text())
        mf = model_flops(cfg, shape)
        terms = H.roofline_terms(cost["flops"], cost["bytes_accessed"],
                                 coll["total"], H.peaks(H.V5E))
        rec.update({
            "ok": True, "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "memory": mem, "cost": cost, "collectives": coll,
            "model": mf, "roofline": terms,
            "useful_flops_ratio": (mf["model_flops"] / n_chips / cost["flops"]
                                   if cost["flops"] else None),
        })
        print(f"[OK] {arch} x {shape_name} x {mesh_kind} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s) "
              f"flops/chip={cost['flops']:.3g} "
              f"bytes/chip={cost['bytes_accessed']:.3g} "
              f"coll/chip={coll['total']:.3g} dom={terms['dominant']}")
    except Exception as e:   # noqa: BLE001 — record the failure, keep going
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {e}")
    if save:
        d = os.path.join(RESULTS_DIR, mesh_kind)
        os.makedirs(d, exist_ok=True)
        suffix = f"__{linear_impl}" if linear_impl else ""
        if profile != "tp":
            suffix += f"__{profile}"
        if not remat:
            suffix += "__noremat"
        if bf16_logits:
            suffix += "__bf16logits"
        with open(os.path.join(d, f"{arch}__{shape_name}{suffix}.json"),
                  "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--linear-impl", default=None,
                    choices=(None, "dense", "spm_general", "spm_rotation"))
    ap.add_argument("--profile", default="tp",
                    choices=("tp", "spm_dp", "spm_dp_g", "spm_dp_g2",
                             "spm_feat"))
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--bf16-logits", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sp in arch_shapes(arch):
                cells.append((arch, sp.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    n_fail = 0
    for mesh_kind in meshes:
        for arch, shape_name in cells:
            if args.skip_existing:
                suffix = f"__{args.linear_impl}" if args.linear_impl else ""
                if args.profile != "tp":
                    suffix += f"__{args.profile}"
                fp = os.path.join(RESULTS_DIR, mesh_kind,
                                  f"{arch}__{shape_name}{suffix}.json")
                if os.path.exists(fp):
                    with open(fp) as f:
                        if json.load(f).get("ok"):
                            continue
            rec = run_cell(arch, shape_name, mesh_kind, args.linear_impl,
                           profile=args.profile, remat=not args.no_remat,
                           bf16_logits=args.bf16_logits)
            n_fail += 0 if rec["ok"] else 1
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
