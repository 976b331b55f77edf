"""SPM operator scaling benchmark (paper §5 complexity claim) + kernel
traffic model + fused-vs-unfused end-to-end ``linear_apply``, including the
RECTANGULAR hot shapes (fused q/k/v, d->4d FFN up/down, LM head) that the
rectangular-native kernel serves without XLA pad/slice.

Wall-clock on this CPU container: dense O(n^2) matmul vs SPM O(nL)
composition at growing width (the paper's crossover, Tables 1-2 compute
columns), plus the end-to-end ``linear_apply`` hot path with the fused
full-operator Pallas kernel ON vs OFF, forward and forward+backward.

Off-TPU the fused path runs in interpret mode, so its wall-clock is a
correctness/validation number, NOT a hardware claim (rows are tagged with
the backend).  The TPU claim is reported via the traffic model: the fused
full operator performs 1 HBM read + 1 write of the activation per boundary
run — diag and bias folded in — vs the L+4 round-trips of the per-stage
composition (L stages lowered separately cost L+1, and the d_in multiply,
d_out multiply, and bias add each add one more).

Emits ``BENCH_kernel.json`` (repo root by default) so later PRs have a
trajectory to compare against.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_step
from repro.analysis.recompile import assert_compiles
from repro.core import SPMConfig, init_spm, spm_apply
from repro.core.linear import LinearConfig, init_linear, linear_apply
from repro.core.eligibility import quant_acts_eligible
from repro.core.pairings import default_n_stages, two_level_schedule
from repro.kernels.ops import (pick_block_rows_for_plan, plan_runs,
                               plan_runs_for_rows)
from repro.kernels.spm_stack import vmem_bytes
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import V5E, peaks, sharded_stage_traffic
from repro.parallel.spm_shard import plan_steps

KEY = jax.random.PRNGKey(0)

SHARD_DEVICES = 8   # virtual host devices for the sharded timing subprocess


def bench_width(n: int, batch: int = 256):
    L = default_n_stages(n)
    cfg = SPMConfig(n=n, n_stages=L, variant="general", backward="custom",
                    use_kernel=False)
    p = init_spm(KEY, cfg)
    x = jax.random.normal(KEY, (batch, n))
    w = jax.random.normal(KEY, (n, n)) / n ** 0.5

    spm_f = jax.jit(lambda x: spm_apply(p, x, cfg))
    dense_f = jax.jit(lambda x: x @ w)
    # fwd+bwd (training step shape)
    spm_g = jax.jit(jax.grad(lambda x: jnp.sum(spm_apply(p, x, cfg) ** 2)))
    dense_g = jax.jit(jax.grad(lambda x: jnp.sum((x @ w) ** 2)))
    with assert_compiles(1, spm_f=spm_f, dense_f=dense_f,
                         spm_g=spm_g, dense_g=dense_g):
        t_spm = time_step(spm_f, x)
        t_dense = time_step(dense_f, x)
        tg_spm = time_step(spm_g, x)
        tg_dense = time_step(dense_g, x)
    return {"L": L, "fwd_spm_us": t_spm * 1e6, "fwd_dense_us": t_dense * 1e6,
            "bwd_spm_us": tg_spm * 1e6, "bwd_dense_us": tg_dense * 1e6}


def bench_linear_apply(n: int, batch: int = 64):
    """End-to-end linear_apply (full operator: diag + stages + bias),
    fused Pallas kernel vs unfused XLA composition, fwd and fwd+bwd.

    Off-TPU the fused variant runs the kernels in interpret mode —
    validation wall-clock only."""
    return bench_linear_rect(n, n, batch)


def bench_linear_rect(d_in: int, d_out: int, batch: int = 64):
    """linear_apply for an arbitrary (d_in, d_out), fused vs unfused.  The
    fused path is rectangular-NATIVE (in-kernel zero-fill / partial final
    store); the unfused path pays the XLA pad + slice around the square
    n-wide composition."""
    n = LinearConfig(d_in=d_in, d_out=d_out, impl="spm_general").n
    L = default_n_stages(n)
    mk = lambda uk: LinearConfig(d_in=d_in, d_out=d_out, impl="spm_general",
                                 n_stages=L, backward="custom",
                                 use_kernel=uk)
    cfg0, cfg1 = mk(False), mk(True)
    p = init_linear(KEY, cfg0)
    x = jax.random.normal(KEY, (batch, d_in))

    res = {"n": n, "L": L}
    for tag, cfg in (("unfused", cfg0), ("fused", cfg1)):
        f = jax.jit(lambda x, cfg=cfg: linear_apply(p, x, cfg))
        g = jax.jit(jax.grad(
            lambda p, x, cfg=cfg: jnp.sum(linear_apply(p, x, cfg) ** 2)))
        # the sentinel turns a silent mid-loop retrace (which would time
        # compiles, not steps) into a hard failure of the bench run
        with assert_compiles(1, fwd=f, bwd=g):
            res[f"linear_fwd_{tag}_us"] = time_step(f, x) * 1e6
            res[f"linear_fwdbwd_{tag}_us"] = time_step(g, p, x) * 1e6
    return res


# Rectangular hot shapes of the reproduced architectures (smoke-scaled
# proportions): every one of these was pad-to-n + slice before the
# rectangular-native kernel landed.
RECT_SHAPES = [
    ("qkv_fused", 256, 768),    # d -> 3d fused q/k/v projection
    ("ffn_up", 256, 1024),      # d -> 4d FFN up-projection
    ("ffn_down", 1024, 256),    # 4d -> d FFN down-projection
    ("lm_head", 384, 2048),     # d -> vocab head (d_in << d_out)
]


def rect_traffic(d_in: int, d_out: int, n: int, batch: int, L: int) -> dict:
    """HBM bytes for a rectangular FULL-operator call (f32 activations).

    unfused — XLA pad (read d_in, write n — only issued when d_in < n) +
    the L+4 square round-trips + output slice (read n, write d_out — only
    when d_out < n; n = even_ceil(max) makes one side exactly n).
    fused — reads batch*d_in once, writes batch*d_out once, plus one
    n-wide round-trip per INTERIOR run boundary of the kernel plan (and
    the O(nL) coefficient reads).
    quant — the fused plan with int8 activation I/O and an int8
    coefficient table: every activation byte above moves at width 1
    instead of 4, joined by the per-(row-block, feature-tile) f32 scale
    arrays riding each activation pass and the (L, 1) per-stage
    coefficient scales; diag/bias stay f32.  Only modeled when the int8
    run plan is uniform-tile (``core/eligibility.quant_acts_eligible`` —
    the same rule the kernel path engages under); otherwise the quant
    columns report the f32 bytes and reduction 1.0."""
    strides = tuple(
        SPMConfig(n=n, n_stages=L, variant="general").pairing.strides())
    n_runs = len(plan_runs(n, strides))
    act_n = batch * n * 4
    act_in = batch * d_in * 4
    act_out = batch * d_out * 4
    coeff_bytes = L * (n // 2) * 16 + 3 * n * 4
    unfused = (L + 4) * 2 * act_n
    if d_in < n:
        unfused += act_in + act_n     # pad pass
    if d_out < n:
        unfused += act_n + act_out    # slice pass
    fused = act_in + act_out + (n_runs - 1) * 2 * act_n + coeff_bytes
    runs_q = plan_runs_for_rows(n, strides, batch, 1)
    quant_ok = quant_acts_eligible(runs_q)
    if quant_ok:
        nq = len(runs_q)
        br = pick_block_rows_for_plan(runs_q, batch, 1)
        # one (row_blocks, feature_tiles) f32 scale array per activation
        # pass: the input read, each interior boundary (write + re-read),
        # and the output write
        scale_pass = -(-batch // br) * -(-n // runs_q[0][1]) * 4
        n_passes = 2 * nq
        coeff_q = L * (n // 2) * 4 + L * 4 + 3 * n * 4
        quant = (batch * d_in + batch * d_out
                 + (nq - 1) * 2 * batch * n
                 + n_passes * scale_pass + coeff_q)
    else:
        quant = fused
    return {"n_runs": n_runs, "coeff_bytes": coeff_bytes,
            "unfused_bytes": unfused, "fused_bytes": fused,
            "reduction": unfused / fused,
            "quant_eligible": quant_ok, "quant_bytes": quant,
            "quant_reduction": fused / quant}


# Residual-block hot shapes (d_model, d_ff = 4 * d_model): the
# norm -> up -> activation -> down -> residual chain the block megakernel
# lowers as ONE Pallas region.  Smoke halves them like RECT_SHAPES.
BLOCK_SHAPES = [
    ("ffn_d256", 256, 1024),
    ("ffn_d512", 512, 2048),
]


def block_traffic(d_model: int, d_ff: int, rows: int,
                  L: int | None = None) -> dict:
    """Modeled HBM bytes of one residual FFN block (norm -> SPM up ->
    activation -> SPM down -> residual add), f32 activations.

    perlinear — the per-linear fused plan (the pre-block baseline): the
    RMSNorm round-trips the (rows, d_model) activation, each SPM operator
    runs the rectangular-native fused kernel (``rect_traffic``'s fused
    accounting, coefficients included), the activation is one elementwise
    round-trip of the (rows, d_ff) hidden, and the residual add reads two
    (rows, d_model) operands and writes one.

    block — the megakernel: reads x once, writes y once, plus the (rows,)
    f32 row statistics, both stacks' O(nL) coefficient tables and the
    diag/bias/gamma vectors.  The normalized input, the mid activation,
    and the second stack's input never touch HBM — they live in VMEM for
    the whole chain (``kernels/ops.spm_block_fused``)."""
    n = LinearConfig(d_in=d_model, d_out=d_ff, impl="spm_general").n
    L = L if L is not None else default_n_stages(n)
    up = rect_traffic(d_model, d_ff, n, rows, L)
    down = rect_traffic(d_ff, d_model, n, rows, L)
    act_d = rows * d_model * 4
    act_ff = rows * d_ff * 4
    perlinear = (2 * act_d                   # norm round-trip
                 + up["fused_bytes"]
                 + 2 * act_ff                # activation round-trip
                 + down["fused_bytes"]
                 + 3 * act_d)                # residual: read y + x, write
    coeff = L * (n // 2) * 16 + 3 * n * 4    # one stack's tables + vecs
    block = 2 * act_d + rows * 4 + 2 * coeff + n * 4   # + rstd + gamma
    return {"n": n, "L": L,
            "perlinear_bytes": perlinear, "block_bytes": block,
            "reduction": perlinear / block}


def bench_block(d_model: int, d_ff: int, batch: int = 16):
    """End-to-end residual FFN block (norm -> up -> gelu -> down ->
    residual): the block megakernel vs the per-linear fused composition,
    fwd and fwd+bwd.  Off-TPU the fused path runs in interpret mode —
    validation wall-clock only (the HBM claim rides ``block_traffic``)."""
    from repro.layers.ffn import FFNConfig, ffn_block_apply, init_ffn
    from repro.layers.norms import init_rms_norm

    mk = lambda fuse: FFNConfig(
        d_model=d_model, d_ff=d_ff, linear_impl="spm_general",
        activation="gelu", spm_backward="custom", spm_use_kernel=True,
        spm_block_fuse=fuse)
    cfg0, cfg1 = mk(False), mk(True)
    p = init_ffn(KEY, cfg0)
    np_ = init_rms_norm(d_model)
    x = jax.random.normal(KEY, (batch, d_model))

    res = {}
    for tag, cfg in (("perlinear", cfg0), ("block", cfg1)):
        f = jax.jit(lambda x, cfg=cfg: ffn_block_apply(p, np_, x, cfg))
        g = jax.jit(jax.grad(
            lambda p, x, cfg=cfg: jnp.sum(
                ffn_block_apply(p, np_, x, cfg) ** 2)))
        with assert_compiles(1, fwd=f, bwd=g):
            res[f"block_fwd_{tag}_us"] = time_step(f, x) * 1e6
            res[f"block_fwdbwd_{tag}_us"] = time_step(g, p, x) * 1e6
    return res


def traffic_model(n: int, batch: int, L: int,
                  kernel_rows: int | None = None) -> dict:
    """HBM bytes per SQUARE full-operator call (f32 activations).

    Byte counts come from ``rect_traffic(n, n, ...)`` — the square
    operator is the d_in == d_out == n special case (no pad/slice passes,
    fused = n_runs round-trips + coefficients), so the two BENCH sections
    share one accounting.  Adds the round-trip counts, the pre-fold
    ``kernel_only`` baseline (stage stack fused, diag/bias still separate
    XLA passes), and the block_rows/VMEM configuration spm_stack_fused
    actually runs (per-run budgeting — ops.pick_block_rows_for_plan) at
    ``kernel_rows`` rows: the batch the fused linear rows of the SAME
    record are timed with, which caps the row block."""
    act = batch * n * 4
    strides = tuple(
        SPMConfig(n=n, n_stages=L, variant="general").pairing.strides())
    runs = plan_runs(n, strides)
    t = rect_traffic(n, n, n, batch, L)
    n_runs = t["n_runs"]
    kernel_only = (n_runs + 3) * 2 * act + t["coeff_bytes"]
    max_tile = max(tile for _, tile in runs)
    br = pick_block_rows_for_plan(runs, kernel_rows or batch, 4)
    return {"unfused_roundtrips": L + 4,
            "fused_roundtrips": n_runs,
            "n_runs": n_runs,
            "unfused_bytes": t["unfused_bytes"],
            "kernel_only_bytes": kernel_only,
            "fused_bytes": t["fused_bytes"],
            "reduction": t["reduction"],
            "reduction_vs_kernel_only": kernel_only / t["fused_bytes"],
            "quant_eligible": t["quant_eligible"],
            "quant_bytes": t["quant_bytes"],
            "quant_reduction": t["quant_reduction"],
            "max_tile": max_tile,
            "block_rows": br,
            "vmem_bytes": max(vmem_bytes(br, tile, len(rs))
                              for rs, tile in runs)}


def sharded_model(n: int, batch: int, L: int,
                  n_shards: int = SHARD_DEVICES,
                  in_width: int | None = None,
                  out_width: int | None = None) -> dict:
    """Modeled sharded-vs-replicated traffic for one two_level operator.

    replicated — one chip runs the full n-wide fused plan (PR 1/2 model).
    sharded    — each of n_shards chips runs the n_local-wide slab; cross
    stages each move the slab once over ICI (collective_permute partner
    exchange).  Bytes are per chip, f32 activations.

    The sharded traffic is modeled THREE ways for the full operator (diag
    + bias, plus any rectangular widths): ``modeled`` is the kernel-native
    step-serial executor (diag/bias folded into the boundary kernel runs,
    the rectangular input window-read in VMEM), ``modeled_overlap`` the
    overlap-scheduled executor (row-block pipelined cross-shard exchanges
    — same HBM, but the per-stage permute bytes split into exposed vs
    hidden), and ``modeled_pr3`` the PR 3 baseline (explicit elementwise
    diag/bias ops in the shard body and an XLA pad/slice around the
    square core).  ``boundary_reduction`` is the folded/pre-fold HBM
    ratio; ``exposed_reduction`` the serial/overlap exposed-comm ratio.
    """
    strides = tuple(two_level_schedule(n, L, n_shards).strides())
    steps = plan_steps(n, strides, n_shards)
    n_local = n // n_shards
    # mirror the executor's width normalization (spm_apply_sharded): a
    # full-width side is square — no boundary op exists to charge for
    if in_width == n:
        in_width = None
    if out_width == n:
        out_width = None
    kw = dict(use_diag=True, use_bias=True,
              in_width=in_width, out_width=out_width, hw=peaks(V5E))
    sh = sharded_stage_traffic(n_local, batch, steps,
                               fold_boundaries=True, **kw)
    sh_ov = sharded_stage_traffic(n_local, batch, steps,
                                  fold_boundaries=True, overlap=True, **kw)
    sh_pr3 = sharded_stage_traffic(n_local, batch, steps,
                                   fold_boundaries=False, **kw)
    act = batch * n * 4
    n_runs = len(plan_runs(n, strides))
    coeff_bytes = L * (n // 2) * 16 + 3 * n * 4
    rep_bytes = 2 * n_runs * act + coeff_bytes
    rep_s = rep_bytes / peaks(V5E)["hbm_bw"]
    shard_s = sh["memory_s"] + sh["collective_s"]
    return {"n": n, "L": L, "n_shards": n_shards, "n_local": n_local,
            "in_width": in_width, "out_width": out_width,
            "n_cross_stages": sum(1 for s in steps if s[0] == "cross"),
            "n_local_runs": sum(1 for s in steps if s[0] == "local"),
            "modeled": sh,
            "modeled_overlap": sh_ov,
            "modeled_pr3": sh_pr3,
            "boundary_reduction": (sh_pr3["hbm_bytes_per_chip"]
                                   / sh["hbm_bytes_per_chip"]),
            "exposed_reduction": (
                sh["exposed_permute_bytes_per_chip"]
                / max(sh_ov["exposed_permute_bytes_per_chip"], 1)),
            "replicated_hbm_bytes": rep_bytes,
            "replicated_s": rep_s,
            "sharded_s": shard_s,
            "speedup_model": rep_s / shard_s if shard_s else None}


def time_sharded(n: int, batch: int, L: int,
                 n_shards: int = SHARD_DEVICES, timeout: int = 600) -> dict:
    """Wall-clock the distributed executor over ``n_shards`` devices.

    On an accelerator backend this process already holds the chips, and
    a child process could not reach them: time in-process over the real
    devices, or return a ``{"skipped": reason}`` row (the reason is
    printed) when the backend has fewer than ``n_shards``.  On the CPU
    backend the forced host-device count must be set before jax
    initializes, and this process already owns a 1-device backend
    (conftest's rule), so the measurement re-execs THIS file with
    ``--sharded-worker`` in a child whose XLA_FLAGS request ``n_shards``
    host devices.  A failed measurement raises."""
    if jax.default_backend() != "cpu":
        if jax.device_count() < n_shards:
            reason = (f"needs {n_shards} devices, the "
                      f"{jax.default_backend()} backend has "
                      f"{jax.device_count()}")
            print(f"# sharded timing skipped: {reason}")
            return {"skipped": reason}
        return _time_sharded_here(n, batch, L, n_shards)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_shards}")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--sharded-worker", f"{n},{batch},{L},{n_shards}"]
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"sharded timing worker failed "
                           f"(exit {r.returncode}): "
                           f"{(r.stderr or r.stdout)[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _time_sharded_here(n: int, batch: int, L: int, n_shards: int) -> dict:
    """Time sharded vs replicated spm_apply on the same params over this
    process's first ``n_shards`` devices.  ``use_kernel`` stays auto: the
    XLA composition on CPU, the fused kernel on TPU."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh
    from repro.parallel.ctx import activation_sharding

    cfg = SPMConfig(n=n, n_stages=L, schedule="two_level",
                    n_shards=n_shards, backward="custom", use_kernel=None,
                    overlap=False)
    cfg_ov = dataclasses.replace(cfg, overlap=True)
    p = init_spm(KEY, cfg)
    x = jax.random.normal(KEY, (batch, n))
    mesh = Mesh(np.asarray(jax.devices()[:n_shards]).reshape(n_shards,),
                ("model",))
    rep_f = jax.jit(lambda x: spm_apply(p, x, cfg))
    rep_g = jax.jit(jax.grad(lambda x: jnp.sum(spm_apply(p, x, cfg) ** 2)))
    out = {"n": n, "batch": batch, "L": L, "n_shards": n_shards,
           "devices": jax.device_count(),
           "replicated_fwd_us": time_step(rep_f, x) * 1e6,
           "replicated_fwdbwd_us": time_step(rep_g, x) * 1e6}
    with activation_sharding(mesh, shard_feature=True):
        sh_f = jax.jit(lambda x: spm_apply(p, x, cfg))
        sh_g = jax.jit(jax.grad(
            lambda x: jnp.sum(spm_apply(p, x, cfg) ** 2)))
        out["sharded_fwd_us"] = time_step(sh_f, x) * 1e6
        out["sharded_fwdbwd_us"] = time_step(sh_g, x) * 1e6
        # overlap schedule (per-block ppermute transport on host devices —
        # correctness wall-clock only; the ICI overlap claim rides the
        # exposed/hidden traffic model)
        ov_f = jax.jit(lambda x: spm_apply(p, x, cfg_ov))
        ov_g = jax.jit(jax.grad(
            lambda x: jnp.sum(spm_apply(p, x, cfg_ov) ** 2)))
        out["sharded_overlap_fwd_us"] = time_step(ov_f, x) * 1e6
        out["sharded_overlap_fwdbwd_us"] = time_step(ov_g, x) * 1e6
    return out


def run_sharded_worker(spec: str) -> None:
    """Child entry (forced multi-device CPU backend): print one JSON line
    of ``_time_sharded_here``."""
    n, batch, L, n_shards = map(int, spec.split(","))
    print(json.dumps(_time_sharded_here(n, batch, L, n_shards)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: one width, small batches")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--linear-batch", type=int, default=64,
                    help="batch for the end-to-end linear_apply rows "
                         "(kept small: interpret mode off-TPU)")
    ap.add_argument("--out", default="BENCH_kernel.json",
                    help="JSON trajectory output ('' to skip)")
    ap.add_argument("--skip-fused-timing", action="store_true",
                    help="traffic model only (no interpret-mode wall-clock)")
    ap.add_argument("--skip-sharded-timing", action="store_true",
                    help="modeled sharded rows only (no timing subprocess)")
    ap.add_argument("--sharded-worker", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.sharded_worker:
        run_sharded_worker(args.sharded_worker)
        return
    widths = (512, 1024, 2048, 4096) if args.full else (256, 512, 1024)
    rect_shapes = RECT_SHAPES
    if args.smoke:
        widths = (256,)
        rect_shapes = [(t, i // 2, o // 2) for t, i, o in RECT_SHAPES]
        args.batch = min(args.batch, 64)
        args.linear_batch = min(args.linear_batch, 16)
    backend = jax.default_backend()

    print(f"# SPM vs dense scaling + fused-operator bench (backend={backend})")
    print("n,L,fwd_dense_us,fwd_spm_us,fwd_speedup,"
          "bwd_dense_us,bwd_spm_us,bwd_speedup,hbm_reduction,"
          "fused_roundtrips,unfused_roundtrips,vmem_bytes")
    records = []
    for n in widths:
        r = bench_width(n, args.batch)
        t = traffic_model(n, args.batch, r["L"],
                          kernel_rows=args.linear_batch)
        rec = {"n": n, **r, "traffic": t}
        if not args.skip_fused_timing:
            rec.update(bench_linear_apply(n, args.linear_batch))
        records.append(rec)
        print(f"{n},{r['L']},{r['fwd_dense_us']:.0f},{r['fwd_spm_us']:.0f},"
              f"{r['fwd_dense_us']/r['fwd_spm_us']:.2f}x,"
              f"{r['bwd_dense_us']:.0f},{r['bwd_spm_us']:.0f},"
              f"{r['bwd_dense_us']/r['bwd_spm_us']:.2f}x,"
              f"{t['reduction']:.1f}x,{t['fused_roundtrips']},"
              f"{t['unfused_roundtrips']},{t['vmem_bytes']}")
        emit(f"kernel/n{n}/spm_fwd", r["fwd_spm_us"],
             f"dense={r['fwd_dense_us']:.0f}us")
        if not args.skip_fused_timing:
            emit(f"kernel/n{n}/linear_fused_fwd", rec["linear_fwd_fused_us"],
                 f"unfused={rec['linear_fwd_unfused_us']:.0f}us "
                 f"(interpret={backend != 'tpu'})")

    # rectangular hot shapes: fused (rectangular-native kernel) vs unfused
    # (XLA pad + square composition + slice), fwd and fwd+bwd
    print("# rectangular hot shapes (d_in,d_out,n,L,"
          "fwd_unfused_us,fwd_fused_us,fwdbwd_unfused_us,fwdbwd_fused_us,"
          "hbm_reduction,quant_bytes,quant_reduction)")
    rect_records = []
    for tag, d_in, d_out in rect_shapes:
        rr = {"shape": tag, "d_in": d_in, "d_out": d_out}
        if not args.skip_fused_timing:
            rr.update(bench_linear_rect(d_in, d_out, args.linear_batch))
        else:
            rr["n"] = LinearConfig(d_in=d_in, d_out=d_out,
                                   impl="spm_general").n
            rr["L"] = default_n_stages(rr["n"])
        rr["traffic"] = rect_traffic(d_in, d_out, rr["n"],
                                     args.linear_batch, rr["L"])
        rect_records.append(rr)
        if not args.skip_fused_timing:
            print(f"{tag},{d_in},{d_out},{rr['n']},{rr['L']},"
                  f"{rr['linear_fwd_unfused_us']:.0f},"
                  f"{rr['linear_fwd_fused_us']:.0f},"
                  f"{rr['linear_fwdbwd_unfused_us']:.0f},"
                  f"{rr['linear_fwdbwd_fused_us']:.0f},"
                  f"{rr['traffic']['reduction']:.1f}x,"
                  f"{rr['traffic']['quant_bytes']},"
                  f"{rr['traffic']['quant_reduction']:.2f}x")
            emit(f"kernel/rect_{tag}/linear_fused_fwd",
                 rr["linear_fwd_fused_us"],
                 f"unfused={rr['linear_fwd_unfused_us']:.0f}us "
                 f"(interpret={backend != 'tpu'})")

    # residual-block fusion: the whole norm -> up -> act -> down ->
    # residual chain as ONE Pallas region vs the per-linear fused plan
    print("# residual-block fusion (shape,d_model,d_ff,n,L,"
          "fwd_perlinear_us,fwd_block_us,fwdbwd_perlinear_us,"
          "fwdbwd_block_us,perlinear_bytes,block_bytes,hbm_reduction)")
    block_shapes = BLOCK_SHAPES
    if args.smoke:
        block_shapes = [(t, d // 2, f // 2) for t, d, f in BLOCK_SHAPES]
    block_records = []
    for tag, d_model, d_ff in block_shapes:
        br = {"shape": tag, "d_model": d_model, "d_ff": d_ff}
        br["traffic"] = block_traffic(d_model, d_ff, args.linear_batch)
        if not args.skip_fused_timing:
            br.update(bench_block(d_model, d_ff, args.linear_batch))
        block_records.append(br)
        t = br["traffic"]
        if not args.skip_fused_timing:
            print(f"{tag},{d_model},{d_ff},{t['n']},{t['L']},"
                  f"{br['block_fwd_perlinear_us']:.0f},"
                  f"{br['block_fwd_block_us']:.0f},"
                  f"{br['block_fwdbwd_perlinear_us']:.0f},"
                  f"{br['block_fwdbwd_block_us']:.0f},"
                  f"{t['perlinear_bytes']},{t['block_bytes']},"
                  f"{t['reduction']:.2f}x")
            emit(f"kernel/block_{tag}/fused_fwd", br["block_fwd_block_us"],
                 f"perlinear={br['block_fwd_perlinear_us']:.0f}us "
                 f"(interpret={backend != 'tpu'})")
        else:
            print(f"{tag},{d_model},{d_ff},{t['n']},{t['L']},,,,,"
                  f"{t['perlinear_bytes']},{t['block_bytes']},"
                  f"{t['reduction']:.2f}x")

    # sharded (two_level over 8 virtual devices) vs replicated: modeled
    # per-stage collective_permute bytes next to the HBM traffic model,
    # plus an interpret-safe wall-clock from a forced-device-count child
    # for the smallest width.
    print("# sharded vs replicated (n,L,n_shards,cross_stages,"
          "permute_bytes/chip,exposed_serial,exposed_overlap,"
          "exposed_reduction,hbm_bytes/chip,pr3_hbm_bytes/chip,"
          "boundary_reduction,replicated_bytes,model_speedup)")
    sharded_records = []
    shapes = [(n, None, None, None) for n in widths]
    # one rectangular sharded row (FFN-up-like proportions): the windowed
    # kernel boundaries drop the PR 3 pad/slice terms entirely
    shapes.append((widths[0], widths[0] - widths[0] // 4, widths[0], None))
    # and one local-ending row: L padded to end the two_level cycle on a
    # LOCAL step, so d_out/bias fold into the last kernel run (the
    # default-L schedules end on a cross stage and fold them into the mix
    # epilogue's role vectors instead — both shapes are output-fold-free
    # in the model; this row keeps the kernel-run fold covered)
    n0 = widths[0]
    for L_fold in range(default_n_stages(n0), default_n_stages(n0) + 16):
        st = plan_steps(n0, tuple(two_level_schedule(
            n0, L_fold, SHARD_DEVICES).strides()), SHARD_DEVICES)
        if st[0][0] == "local" and st[-1][0] == "local":
            shapes.append((n0, None, None, L_fold))
            break
    for i, (n, iw, ow, L_override) in enumerate(shapes):
        L = L_override if L_override is not None else default_n_stages(n)
        sr = sharded_model(n, args.batch, L, in_width=iw, out_width=ow)
        if i == 0 and not (args.skip_fused_timing
                           or args.skip_sharded_timing):
            # same batch as the modeled row: the JSON record's modeled
            # seconds and measured microseconds describe ONE workload
            sr["timing"] = time_sharded(n, args.batch, L)
        sharded_records.append(sr)
        m, mo = sr["modeled"], sr["modeled_overlap"]
        print(f"{n},{sr['L']},{sr['n_shards']},{sr['n_cross_stages']},"
              f"{m['permute_bytes_per_chip']},"
              f"{m['exposed_permute_bytes_per_chip']},"
              f"{mo['exposed_permute_bytes_per_chip']},"
              f"{sr['exposed_reduction']:.2f}x,"
              f"{m['hbm_bytes_per_chip']},"
              f"{sr['modeled_pr3']['hbm_bytes_per_chip']},"
              f"{sr['boundary_reduction']:.2f}x,"
              f"{sr['replicated_hbm_bytes']},{sr['speedup_model']:.2f}x")
        if sr.get("timing") and "skipped" not in sr["timing"]:
            t = sr["timing"]
            emit(f"kernel/n{n}/sharded_fwd", t["sharded_fwd_us"],
                 f"replicated={t['replicated_fwd_us']:.0f}us "
                 f"devices={t['devices']}")

    if args.out:
        payload = {
            "generated_by": "benchmarks/kernel_bench.py",
            "backend": backend,
            "batch": args.batch,
            "linear_batch": args.linear_batch,
            "note": ("fused wall-clock is interpret-mode (validation only) "
                     "off-TPU; the traffic model carries the HBM claim"),
            "results": records,
            "rect_results": rect_records,
            "block_results": block_records,
            "sharded_results": sharded_records,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
