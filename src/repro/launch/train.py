"""End-to-end training driver with recovery orchestration.

CPU-scale with ``--smoke``; without it the arch's full published config
(``chip_smoke.py`` drives qwen3-1.7b this way on one TPU chip).
Features exercised here: deterministic resumable data, NaN-guarded
steps, atomic keep-N checkpoints with verified-integrity restore (corrupt
checkpoints are quarantined and the restore walks back to the newest
valid step), fault-policy rollback that coherently rewinds the loop
counter / data cursor / LR schedule, and ``run_with_recovery`` restarts
with exponential backoff around the whole loop.  ``--chaos-spec`` arms
deterministic fault injection (train/chaos.py) so every one of those
paths can be exercised on demand:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1 \
      --chaos-spec 'nan@13+5;corrupt@18:bitflip;preempt@19'

Tests drive the same code through ``train(args)`` (no subprocess
needed); it returns the final state for parity assertions, and its
``on_step`` observer sees every step's host metrics, wall time and
jitted step function.
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, get_config, get_smoke, with_overrides,
                           with_quantized_io)
from repro.data.char_corpus import build_corpus
from repro.data.loader import DeterministicLoader
from repro.models import causal_lm as LM
from repro.models import transformer as T
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import OptimizerConfig
from repro.train import (FaultEventLog, FaultPolicy, RESUME_LATEST,
                         StragglerDetector, latest_valid_step,
                         make_pod_train_step, make_train_state,
                         make_train_step, restore_checkpoint,
                         run_with_recovery, save_checkpoint)
from repro.train.chaos import ChaosSchedule


def make_batch_fn(cfg: T.ModelConfig, seq_len: int, corpus: np.ndarray):
    n = len(corpus) - seq_len - 1

    def batch_fn(key, global_batch):
        starts = jax.random.randint(key, (global_batch,), 0, n)
        idx = starts[:, None] + jnp.arange(seq_len + 1)[None, :]
        chunk = jnp.asarray(corpus)[idx]
        toks = chunk[:, :-1].astype(jnp.int32) % cfg.vocab_size
        labels = chunk[:, 1:].astype(jnp.int32) % cfg.vocab_size
        batch = {"labels": labels}
        if cfg.input_kind == "tokens":
            batch["tokens"] = toks
        else:
            # modality-frontend stub: hash tokens into embeddings
            table = jax.random.normal(jax.random.PRNGKey(1),
                                      (cfg.vocab_size, cfg.d_model))
            batch["embeds"] = table[toks]
            if cfg.rope_kind == "mrope":
                pos = jnp.broadcast_to(jnp.arange(seq_len),
                                       toks.shape)
                batch["positions"] = jnp.broadcast_to(
                    pos, (3,) + toks.shape)
        return batch

    return batch_fn


def build_parser() -> argparse.ArgumentParser:
    """CLI for the driver (shared with tests, which build an args
    namespace via ``build_parser().parse_args([...])``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--linear-impl", default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize", action="store_true",
                    help="int8 SPM quantization: activation I/O on the "
                         "fused kernel path + per-stage-scaled coefficient "
                         "tables (configs.with_quantized_io; see "
                         "docs/quantization.md)")
    ap.add_argument("--pod-dp", type=int, default=0,
                    help="data-parallel pod size: >1 runs the train step "
                         "inside a shard_map over a ('pod',) mesh of that "
                         "many devices (batch must divide by it)")
    ap.add_argument("--compress-pod-grads", action="store_true",
                    help="with --pod-dp: reduce gradients through the int8 "
                         "error-feedback compressed psum instead of a "
                         "plain pmean (optim/compression.py)")
    ap.add_argument("--chaos-spec", default="",
                    help="deterministic fault-injection plan, e.g. "
                         "'nan@13+5;corrupt@18:bitflip;preempt@19' "
                         "(see train/chaos.py)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--event-log", default="",
                    help="fault-event JSONL path (default: "
                         "<ckpt-dir>/events.jsonl when --ckpt-dir is set)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restart budget for run_with_recovery")
    ap.add_argument("--backoff-base", type=float, default=0.5)
    return ap


# on_step(step, host metrics, wall seconds, jitted step function)
StepObserver = Callable[[int, dict, float, Callable], None]


def train(args: argparse.Namespace,
          event_log: Optional[FaultEventLog] = None,
          chaos: Optional[ChaosSchedule] = None,
          on_step: Optional[StepObserver] = None) -> dict:
    """Run the full training job described by ``args`` and return the
    final train state.  Builds the recovery orchestration: the inner
    ``loop(resume)`` holds all step/rollback logic, ``run_with_recovery``
    restarts it on failure with exponential backoff and a restart budget.

    ``event_log`` / ``chaos`` override the ones built from ``args``
    (tests pass a shared ChaosSchedule so fire-once state survives a
    simulated process death across two ``train`` calls).  ``on_step(s,
    metrics, seconds, step_fn)`` is called after every executed step with
    its host-side metrics, its wall time (dispatch to ``device_get``) and
    the jitted step function that ran it."""
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.linear_impl:
        cfg = with_overrides(cfg, linear_impl=args.linear_impl)
    if getattr(args, "quantize", False):
        cfg = with_quantized_io(cfg)
    n_pod = max(getattr(args, "pod_dp", 0), 0)
    if getattr(args, "compress_pod_grads", False):
        cfg = with_overrides(cfg, compress_pod_grads=True)
    if n_pod > 1 and args.batch % n_pod:
        raise ValueError(f"--batch {args.batch} must divide by "
                         f"--pod-dp {n_pod}")
    print(f"arch={cfg.name} impl={cfg.linear_impl} "
          f"steps={args.steps} B={args.batch} T={args.seq}"
          + (f" pod={n_pod}"
             f"{' (compressed grads)' if cfg.compress_pod_grads else ''}"
             if n_pod > 1 else ""))

    if event_log is None:
        path = args.event_log or (os.path.join(args.ckpt_dir,
                                               "events.jsonl")
                                  if args.ckpt_dir else None)
        event_log = FaultEventLog(path)
    if chaos is None and args.chaos_spec:
        chaos = ChaosSchedule.parse(args.chaos_spec, seed=args.chaos_seed)

    corpus = build_corpus(200_000, seed=args.seed)

    def fresh_loader() -> DeterministicLoader:
        return DeterministicLoader(make_batch_fn(cfg, args.seq, corpus),
                                   args.batch, seed=args.seed)

    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 20, 1))
    # chaos_guard is always on: with poison=0 the step is bit-identical
    # to a guard-free build, and the single compiled step serves healthy
    # and poisoned iterations alike.
    loss_fn = lambda p, b: LM.lm_loss(p, b, cfg)
    if n_pod > 1:
        from jax.sharding import Mesh
        devs = jax.devices()
        if len(devs) < n_pod:
            raise ValueError(f"--pod-dp {n_pod} needs {n_pod} devices, "
                             f"have {len(devs)}")
        mesh = Mesh(np.asarray(devs[:n_pod]).reshape(n_pod), ("pod",))
        step_fn = jax.jit(make_pod_train_step(
            loss_fn, opt_cfg, mesh, compress=cfg.compress_pod_grads,
            accum_steps=args.accum, chaos_guard=True))
    else:
        step_fn = jax.jit(make_train_step(
            loss_fn, opt_cfg, accum_steps=args.accum, chaos_guard=True))

    def init_state() -> dict:
        params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
        state = make_train_state(
            params,
            ef_pod=n_pod if (n_pod > 1 and cfg.compress_pod_grads) else 0)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"params: {n_params:,}")
        return state

    def try_restore(state: dict, loader: DeterministicLoader,
                    required: bool):
        """Restore the newest VALID checkpoint, or fall back to a fresh
        start.  Returns (state, start_step, loader).  ``required`` marks
        an explicit resume intent (rollback / restart): finding nothing
        then is an event worth logging, not just a cold start."""
        step = (latest_valid_step(args.ckpt_dir, event_log=event_log)
                if args.ckpt_dir else None)
        if step is None:
            if required:
                print("!! no valid checkpoint to resume from; "
                      "restarting from scratch")
                event_log.emit("resume_fallback_fresh")
            return state, 0, loader
        state, extra = restore_checkpoint(
            args.ckpt_dir, state, step=step, event_log=event_log)
        # LR schedule rewinds automatically: it is driven by opt.count
        # inside the restored state.  The loop counter and data cursor
        # rewind here.
        if not loader.resume(extra.get("cursor")):
            event_log.emit("cursor_missing", step=step)
        start = int(extra.get("cursor", {}).get("step", step))
        print(f"resumed from step {start}")
        return state, start, loader

    def loop(resume: Optional[int]) -> dict:
        """One attempt at the training loop.  ``resume=None`` cold-starts
        (auto-resuming if checkpoints exist); ``RESUME_LATEST`` is
        run_with_recovery's explicit restore instruction after a crash."""
        loader = fresh_loader()
        state, start, loader = try_restore(
            init_state(), loader, required=resume == RESUME_LATEST)

        policy = FaultPolicy()
        straggler = StragglerDetector(event_log=event_log)
        t0 = time.time()
        s = start
        while s < args.steps:
            if chaos is not None:
                chaos.pre_step(s)
            batch = loader.batch_at(s)
            poison = chaos.poison(s) if chaos is not None else 0.0
            t_step = time.time()
            state, metrics = step_fn(state, batch, poison)
            metrics = jax.device_get(metrics)
            dt_step = time.time() - t_step
            straggler.observe(s, dt_step)
            if on_step is not None:
                on_step(s, metrics, dt_step, step_fn)
            if metrics.get("skipped"):
                event_log.emit("skip", step=s, cause="non-finite grads")
            if policy.on_metrics(metrics):
                # Coherent rollback: state, loop counter, and data
                # cursor all rewind to the restored step (or to a fresh
                # start when no checkpoint survives).
                print("!! rollback: too many consecutive skipped steps")
                event_log.emit("rollback", step=s,
                               cause=f"{policy.consecutive_skips} "
                                     "consecutive skips")
                state, s, loader = try_restore(
                    init_state(), fresh_loader(), required=True)
                policy.reset()
                continue
            s += 1
            if s % args.log_every == 0:
                dt = (time.time() - t0) / max(s - start, 1)
                print(f"step {s:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"{dt*1e3:.0f} ms/step")
            if args.ckpt_dir and s % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, s, state,
                                extra={"cursor": {"seed": args.seed,
                                                  "step": s}})
            if chaos is not None:
                chaos.post_step(s - 1, args.ckpt_dir or None,
                                event_log=event_log)
        print(f"done in {time.time()-t0:.1f}s "
              f"(skips={policy.total_skips})")
        return state

    return run_with_recovery(loop, max_restarts=args.max_restarts,
                             backoff_base=args.backoff_base,
                             event_log=event_log)


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    train(args)


if __name__ == "__main__":
    main()
