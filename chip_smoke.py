"""Bring-up smoke of the main path on TPU, at qwen3-1.7b's full width.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: the feature-sharded
                                      # executor only, vs one chip

One process owns the chip(s) for the whole run; it starts no other.
Phases (one chip):

* device  — exits non-zero unless JAX's first device is a TPU.
* kernels — fused SPM fwd+grad through ``linear_apply`` at the model's
  projection shapes (plus the 2048 -> 4096 fused-qkv width) against the
  ``kernels/ref.py`` oracle, within the full-operator bounds of
  ``tests/test_kernels.py``.  bf16 activations are held against
  ``spm_runs_ref``, which stores the activation in bf16 at the same run
  boundaries as the fused path; shapes that plan more than one run are
  also run with f32 activations against the unrounded oracle.  The
  compiled HLO must hold a ``tpu_custom_call`` (an XLA fallback fails
  the phase).
* train   — ``launch.train.train`` on the full config (no ``--smoke``)
  for a few steps: finite losses, no restart, first-step loss within
  ``TRAIN_LOSS_RTOL`` of a ``use_kernel=False`` build, kernel in the
  step's HLO.
* serve   — ``ContinuousBatchingEngine`` over 4 slots: every request
  finishes with its token count and in-vocab ids; kernel-path prefill
  logits, and the logits of one decode step from that prefill's cache,
  within ``LOGITS_REL_L2`` of the ``use_kernel=False`` build.

``--chips 4`` runs only the two_level feature-sharded executor
(``parallel/spm_shard.py``, ``overlap=True``: the in-kernel RDMA
transport on TPU) on a ("model",) mesh of four chips at n = 2048 and
4096, fwd + grads, against the same parameters unsharded on one chip.

Lines starting with ``[smoke reading]`` are this run's readings (step
times, tokens/s, compile seconds, peak device memory): smoke readings,
not benchmark metrics.  The last stdout line is the JSON verdict; it is
printed only when every phase passed.  Weights are random, from
``--seed``; nothing is downloaded.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
KERNEL_ROWS = 2048          # one train batch: 2 sequences x 1024 tokens
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 4
SERVE_SLOTS, SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 6, 128, 32
# (y, every grad) bounds of tests/test_kernels.py's full-operator tests
# (test_fused_full_operator_matches_ref / _grads_match_autodiff), by
# activation dtype
KERNEL_TOLS = {"bfloat16": (4e-2, 6e-2), "float32": (1e-4, 1e-4)}
# Model-level bf16 budgets against the use_kernel=False build.  That
# build rounds every stage of every SPM linear to bf16 (12 per linear,
# 7 linears per layer, 28 layers); the kernel path rounds only at run
# boundaries, so the two drift apart by bf16 noise, not by a fault.
TRAIN_LOSS_RTOL = 1e-2      # |loss - loss_ref| / |loss_ref|
LOGITS_REL_L2 = 5e-2        # ||logits - logits_ref|| / ||logits_ref||
SHARD_Y_TOL, SHARD_G_TOL = 2e-5, 2e-4   # f32 (tests/test_distributed.py)


def reading(phase: str, **kv) -> None:
    """One line of smoke readings (never a benchmark metric)."""
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[smoke reading] {phase} {body}", flush=True)


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_close(name: str, got, want, tol: float) -> float:
    """``|got - want| <= tol * (1 + |want|)`` elementwise (the
    ``assert_allclose(atol=tol, rtol=tol)`` bound); returns the largest
    normalized error for the readings."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite values")
    excess = np.abs(got - want) - tol * (1.0 + np.abs(want))
    if np.max(excess) > 0:
        i = np.unravel_index(np.argmax(excess), excess.shape)
        raise AssertionError(f"{name}: |{got[i]} - {want[i]}| exceeds "
                             f"{tol} * (1 + |want|) at {i}")
    return normalized_err(got, want)


def normalized_err(got, want) -> float:
    """Largest ``|got - want| / (1 + |want|)``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def require_kernel(name: str, hlo_text: str) -> None:
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{name}: no tpu_custom_call in the HLO "
                             "(the SPM linears fell back to XLA)")


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def kernel_sites(cfg):
    """(name, d_in, d_out) of every SPM projection shape the model runs,
    plus the fused-qkv width (its 4096-wide operator plans a one-stage
    run whose tile is the whole operator)."""
    d, q = cfg.d_model, cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    return (("qkv", d, q + 2 * kv), ("q|o", d, q), ("k|v", d, kv),
            ("gate|up", d, cfg.d_ff), ("down", cfg.d_ff, d))


def phase_kernels(jax, cfg, seed: int) -> None:
    import jax.numpy as jnp

    from repro.core.linear import LinearConfig, init_linear, linear_apply
    from repro.kernels.ops import plan_runs_for_rows
    from repro.kernels.ref import spm_runs_ref

    key = jax.random.PRNGKey(seed)
    for site, (name, d_in, d_out) in enumerate(kernel_sites(cfg)):
        lc = LinearConfig(d_in=d_in, d_out=d_out, impl=cfg.linear_impl,
                          use_bias=False, backward=cfg.spm_backward)
        scfg = lc.spm_config()
        n, strides = scfg.n, scfg.pairing.strides()
        kp, kd, ko, kx, kw = jax.random.split(jax.random.fold_in(key, site),
                                              5)
        p = init_linear(kp, lc)
        p["d_in"] = 1.0 + 0.1 * jax.random.normal(kd, (n,))
        p["d_out"] = 1.0 + 0.1 * jax.random.normal(ko, (n,))
        x32 = jax.random.normal(kx, (KERNEL_ROWS, d_in)).astype(
            jnp.bfloat16).astype(jnp.float32)
        # cotangent weights: bf16 values, so both paths see the same gy
        w = jax.random.normal(kw, (KERNEL_ROWS, d_out)).astype(
            jnp.bfloat16).astype(jnp.float32)

        def loss(p, x, w):
            y = linear_apply(p, x, lc)
            return jnp.sum(y.astype(jnp.float32) * w), y

        def ref_grad(io_dtype, runs):
            def ref_loss(p, x, w):
                xp = jnp.pad(x, ((0, 0), (0, n - d_in)))
                y = spm_runs_ref(xp, p["mix"], runs, io_dtype,
                                 d_in=p["d_in"], d_out=p["d_out"])
                return jnp.sum(y[:, :d_out] * w), y[:, :d_out]
            return jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1),
                                              has_aux=True))

        def plan(dt):
            return [r for r, _ in plan_runs_for_rows(
                n, strides, KERNEL_ROWS, jnp.dtype(dt).itemsize)]

        multi_run = len(plan(jnp.bfloat16)) > 1
        for dt in (jnp.bfloat16, jnp.float32) if multi_run else \
                (jnp.bfloat16,):
            x, runs = x32.astype(dt), plan(dt)
            f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True))
            t0 = time.perf_counter()
            compiled = f.lower(p, x, w).compile()
            compile_s = time.perf_counter() - t0
            require_kernel(f"kernels/{name}", compiled.as_text())
            (_, y), (gp, gx) = compiled(p, x, w)
            jax.block_until_ready(gx)
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(p, x, w))
            run_s = time.perf_counter() - t0
            got = {"y": y, "g_x": gx, **{f"g_{k}": gp[k]
                                         for k in ("mix", "d_in", "d_out")}}
            y_tol, g_tol = KERNEL_TOLS[jnp.dtype(dt).name]
            (_, y_ref), (gp_ref, gx_ref) = ref_grad(dt, runs)(p, x32, w)
            want = {"y": y_ref, "g_x": gx_ref, **{
                f"g_{k}": gp_ref[k] for k in ("mix", "d_in", "d_out")}}
            errs = {k: check_close(f"{name} {jnp.dtype(dt).name} {k}",
                                   got[k], want[k],
                                   y_tol if k == "y" else g_tol)
                    for k in got}
            extra = {}
            if dt == jnp.bfloat16 and multi_run:
                # the same bf16 result against the oracle that keeps the
                # run boundaries in f32: a reading, not a check (it
                # measures the bf16 hand-off itself)
                (_, y_f), (gp_f, gx_f) = ref_grad(jnp.float32, runs)(
                    p, x32, w)
                plain = {"y": y_f, "g_x": gx_f, **{
                    f"g_{k}": gp_f[k] for k in ("mix", "d_in", "d_out")}}
                extra = {f"unrounded_ref_err_{k}":
                         f"{normalized_err(got[k], plain[k]):.2e}"
                         for k in got}
            reading("kernels", site=name, d_in=d_in, d_out=d_out, n=n,
                    stages=len(strides), runs=len(runs), rows=KERNEL_ROWS,
                    io=jnp.dtype(dt).name, compile_s=f"{compile_s:.2f}",
                    fwd_bwd_ms=f"{run_s * 1e3:.3f}",
                    **{f"max_err_{k}": f"{v:.2e}" for k, v in errs.items()},
                    **extra)
    reading("kernels", peak_bytes_in_use=peak_bytes(jax))


def phase_train(jax, seed: int) -> None:
    from repro.configs import get_config
    from repro.data.char_corpus import build_corpus
    from repro.data.loader import DeterministicLoader
    from repro.launch.train import build_parser, make_batch_fn, train
    from repro.models import causal_lm as LM
    from repro.models import transformer as T
    from repro.train import FaultEventLog, make_train_state

    args = build_parser().parse_args([
        "--arch", ARCH, "--steps", str(TRAIN_STEPS),
        "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
        "--seed", str(seed), "--log-every", "1", "--max-restarts", "0"])
    log = FaultEventLog(None)
    steps, step_fns = [], set()

    def on_step(s, metrics, dt, step_fn):
        steps.append((s, metrics, dt))
        step_fns.add(step_fn)

    t0 = time.perf_counter()
    state = train(args, event_log=log, on_step=on_step)
    wall_s = time.perf_counter() - t0
    if log.kinds():
        raise AssertionError(f"train: fault events {log.kinds()}")
    if [s for s, _, _ in steps] != list(range(TRAIN_STEPS)):
        raise AssertionError(f"train: steps run {[s for s, _, _ in steps]}")
    losses = [float(m["loss"]) for _, m, _ in steps]
    if not all(np.isfinite(losses)) or any(m.get("skipped")
                                            for _, m, _ in steps):
        raise AssertionError(f"train: losses {losses}")
    del state

    cfg = get_config(ARCH)
    cfg_ref = get_config(ARCH, use_kernel=False)
    params = T.init_model(jax.random.PRNGKey(seed), cfg)
    batch = DeterministicLoader(
        make_batch_fn(cfg, TRAIN_SEQ, build_corpus(200_000, seed=seed)),
        TRAIN_BATCH, seed=seed).batch_at(0)
    loss_ref = float(jax.jit(lambda p, b: LM.lm_loss(p, b, cfg_ref)[0])(
        params, batch))
    rel = abs(losses[0] - loss_ref) / abs(loss_ref)
    if not rel <= TRAIN_LOSS_RTOL:
        raise AssertionError(f"train: first loss {losses[0]} vs "
                             f"use_kernel=False {loss_ref} (rel {rel:.2e} > "
                             f"{TRAIN_LOSS_RTOL})")
    # the jitted step train() ran, lowered at its step-0 arguments
    (step_fn,) = step_fns
    require_kernel("train step", step_fn.lower(
        make_train_state(params), batch, 0.0).as_text())

    dts = [dt for _, _, dt in steps]
    steady = float(np.mean(dts[1:]))
    reading("train", arch=ARCH, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
            losses=",".join(f"{v:.4f}" for v in losses),
            first_loss_ref=f"{loss_ref:.4f}", first_loss_rel_err=f"{rel:.2e}",
            first_step_s_incl_compile=f"{dts[0]:.2f}",
            step_s=",".join(f"{v:.4f}" for v in dts[1:]),
            tokens_per_s=f"{TRAIN_BATCH * TRAIN_SEQ / steady:.0f}",
            wall_s=f"{wall_s:.1f}", peak_bytes_in_use=peak_bytes(jax))


def phase_serve(jax, seed: int) -> None:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import causal_lm as LM
    from repro.models import transformer as T
    from repro.serve import ContinuousBatchingEngine, Request

    cfg = get_config(ARCH)
    cfg_ref = get_config(ARCH, use_kernel=False)
    k_init, k_prompts, k_sample = jax.random.split(jax.random.PRNGKey(seed),
                                                   3)
    params = T.init_model(k_init, cfg)
    max_len = PROMPT_LEN + NEW_TOKENS
    prompts = [jax.random.randint(jax.random.fold_in(k_prompts, i),
                                  (PROMPT_LEN,), 0, cfg.vocab_size)
               for i in range(SERVE_REQUESTS)]
    reqs = [Request(prompt=pr, max_new_tokens=NEW_TOKENS,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    top_k=0 if i % 2 == 0 else 40, rid=i)
            for i, pr in enumerate(prompts)]
    eng = ContinuousBatchingEngine(cfg, params, slots=SERVE_SLOTS,
                                   max_len=max_len,
                                   cache_dtype=jnp.bfloat16,
                                   base_key=k_sample)
    t0 = time.perf_counter()
    results, stats = eng.serve(reqs, arrival_ticks=[2 * i for i in
                                                    range(len(reqs))])
    wall_s = time.perf_counter() - t0
    for r in reqs:
        res = results[r.rid]
        toks = res["tokens"]
        if len(toks) != NEW_TOKENS or res["finished_tick"] is None:
            raise AssertionError(f"serve: request {r.rid} returned "
                                 f"{len(toks)} tokens")
        if res["flagged"] or not all(0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"serve: request {r.rid} flagged or out of "
                                 f"vocab: {toks}")

    batch = jnp.stack(prompts[:SERVE_SLOTS])

    def prefill_fn(c):
        return jax.jit(lambda p, t: LM.prefill(
            p, c, max_len=max_len, tokens=t, cache_dtype=jnp.bfloat16))

    def decode_fn(c):
        return jax.jit(lambda p, t, cache, i: LM.decode_step(
            p, c, t, cache, i)[0])

    def check_logits(stage, got, want) -> float:
        err = rel_l2(got, want)
        if not err <= LOGITS_REL_L2:
            raise AssertionError(f"serve: {stage} logits rel-L2 {err:.2e} > "
                                 f"{LOGITS_REL_L2} vs use_kernel=False")
        return err

    lowered = prefill_fn(cfg).lower(params, batch)
    require_kernel("serve prefill", lowered.as_text())
    logits, cache = lowered.compile()(params, batch)
    prefill_err = check_logits("prefill", logits,
                               prefill_fn(cfg_ref)(params, batch)[0])
    # one decode step (the tiny-row kernel plan) from the kernel prefill's
    # cache, against the use_kernel=False decode from the same cache
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    index = jnp.full((SERVE_SLOTS,), PROMPT_LEN, jnp.int32)
    lowered = decode_fn(cfg).lower(params, token, cache, index)
    require_kernel("serve decode", lowered.as_text())
    decode_err = check_logits(
        "decode", lowered.compile()(params, token, cache, index),
        decode_fn(cfg_ref)(params, token, cache, index))
    reading("serve", arch=ARCH, slots=SERVE_SLOTS, requests=len(reqs),
            prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS,
            ticks=stats["ticks"], tokens=stats["tokens"],
            wall_s_incl_compile=f"{wall_s:.2f}",
            tokens_per_s_incl_compile=f"{stats['tokens'] / wall_s:.1f}",
            prefill_logits_rel_l2=f"{prefill_err:.2e}",
            decode_logits_rel_l2=f"{decode_err:.2e}",
            peak_bytes_in_use=peak_bytes(jax))


# ---------------------------------------------------------------------------
# four chips: the feature-sharded executor vs one chip
# ---------------------------------------------------------------------------

def phase_sharded(jax, seed: int, n_chips: int) -> None:
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.analysis.hlo_match import (bwd_gather_bound_violations,
                                          permute_only_violations)
    from repro.core.pairings import default_n_stages
    from repro.core.spm import SPMConfig, init_spm, spm_apply
    from repro.parallel.ctx import activation_sharding

    rows = 1024
    mesh = Mesh(np.asarray(jax.devices()[:n_chips]), ("model",))
    one = jax.devices()[0]
    for n in (2048, 4096):
        cfg = SPMConfig(n=n, n_stages=default_n_stages(n),
                        schedule="two_level", n_shards=n_chips,
                        backward="custom", overlap=True)
        ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), n),
                              6)
        p = init_spm(ks[0], cfg)
        p["d_in"] = 1.0 + 0.1 * jax.random.normal(ks[1], (n,))
        p["d_out"] = 1.0 + 0.1 * jax.random.normal(ks[2], (n,))
        p["bias"] = 0.1 * jax.random.normal(ks[3], (n,))
        x = jax.random.normal(ks[4], (rows, n))
        w = jax.random.normal(ks[5], (rows, n))

        def make_grad():
            # a fresh function per mesh context: spm_apply reads the
            # activation_sharding context while tracing, and JAX's trace
            # cache is keyed on the function, not on that context
            def loss(p, x, w):
                y = spm_apply(p, x, cfg)
                return jnp.sum(y * w), y
            return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))

        (gp_ref, gx_ref), y_ref = make_grad()(
            *jax.device_put((p, x, w), one))
        with activation_sharding(mesh, shard_feature=True):
            f_fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg))
            t0 = time.perf_counter()
            hlo_fwd = f_fwd.lower(p, x).compile().as_text()
            compiled = make_grad().lower(p, x, w).compile()
            compile_s = time.perf_counter() - t0
            (gp, gx), y = compiled(p, x, w)
            jax.block_until_ready(gx)
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(p, x, w))
            run_s = time.perf_counter() - t0
        hlo_bwd = compiled.as_text()
        for kern, text in (("spm_overlap_kernel_call", hlo_fwd),
                           ("spm_overlap_bwd_kernel_call", hlo_bwd)):
            if f"{kern})/pallas_call" not in text:
                found = sorted(set(re.findall(
                    r"jit\((spm_\w+)\)/pallas_call", text)))
                raise AssertionError(f"sharded n={n}: {kern} (RDMA overlap "
                                     f"kernel) not in the compiled HLO; "
                                     f"Pallas kernels there: {found}")
        param_bytes = sum(v.size * v.dtype.itemsize for v in p.values())
        bad = (permute_only_violations(hlo_fwd)
               + bwd_gather_bound_violations(hlo_bwd,
                                             param_bytes=param_bytes))
        if bad:
            raise AssertionError(f"sharded n={n}: " + "; ".join(bad))
        errs = {"y": check_close(f"n={n} y", y, y_ref, SHARD_Y_TOL),
                "g_x": check_close(f"n={n} g_x", gx, gx_ref, SHARD_G_TOL)}
        for k in gp:
            errs[f"g_{k}"] = check_close(f"n={n} g_{k}", gp[k], gp_ref[k],
                                         SHARD_G_TOL)
        reading("sharded", n=n, chips=n_chips, rows=rows,
                stages=cfg.n_stages, compile_s=f"{compile_s:.2f}",
                fwd_bwd_ms=f"{run_s * 1e3:.3f}",
                **{f"max_err_{k}": f"{v:.2e}" for k, v in errs.items()})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the feature-sharded executor phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    reading("device", platform=devices[0].platform,
            kind=repr(devices[0].device_kind), count=len(devices),
            compile_cache=enable_compile_cache())
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count_cache_event(event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and \
                name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    phases = ([("sharded", lambda: phase_sharded(jax, args.seed, 4))]
              if args.chips == 4 else
              [("kernels", lambda: phase_kernels(jax, get_config(ARCH),
                                                 args.seed)),
               ("train", lambda: phase_train(jax, args.seed)),
               ("serve", lambda: phase_serve(jax, args.seed))])
    for name, run in phases:
        before = dict(cache_events)
        t0 = time.perf_counter()
        run()
        reading(name, phase_s=f"{time.perf_counter() - t0:.1f}",
                status="passed",
                **{k: v - before[k] for k, v in cache_events.items()})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
