"""Paper Table 1: compositional teacher — Dense vs SPM students.

Sweeps width; reports test accuracy and ms/step for both students under
an identical recipe (same optimizer/lr/batch/steps, paper §9.1).  Quick
mode shrinks widths/steps to finish on this 1-core CPU container; --full
runs the paper's exact widths/steps.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import emit, time_step
from repro.configs.paper import T1_BATCH, T1_CLASSES, student_cfg
from repro.data import DeterministicLoader, TeacherConfig, make_teacher, teacher_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_mlp, mlp_loss
from repro.optim import OptimizerConfig
from repro.train import make_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def run_one(width: int, impl: str, steps: int, batch: int) -> dict:
    tc = TeacherConfig(width=width, n_classes=T1_CLASSES)
    teacher = make_teacher(tc)
    loader = DeterministicLoader(
        lambda k, n: teacher_batch(teacher, tc, k, n), batch, seed=0)
    cfg = student_cfg(width, T1_CLASSES, impl)
    state = make_train_state(init_mlp(KEY, cfg))
    step = jax.jit(make_train_step(
        lambda p, b: mlp_loss(p, b, cfg),
        OptimizerConfig(lr=3e-3, total_steps=steps)))
    b0 = loader.batch_at(0)
    ms = time_step(lambda s, b: step(s, b)[0], state, b0) * 1e3
    for s in range(steps):
        state, _ = step(state, loader.batch_at(s))
    accs = []
    for s in range(10_000, 10_005):
        _, m = mlp_loss(state["params"], loader.batch_at(s), cfg)
        accs.append(float(m["acc"]))
    return {"acc": float(np.mean(accs)), "ms_per_step": ms}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-exact widths/steps (slow on CPU)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    widths = (256, 512, 1024, 2048) if args.full else (128, 256, 512)
    steps = 1200 if args.full else 300
    batch = T1_BATCH if args.full else 128

    print("# Table 1 repro: compositional teacher (hard labels)")
    print("width,dense_acc,spm_acc,delta_acc,dense_ms,spm_ms,speedup")
    for w in widths:
        d = run_one(w, "dense", steps, batch)
        s = run_one(w, "spm_general", steps, batch)
        speed = d["ms_per_step"] / max(s["ms_per_step"], 1e-9)
        print(f"{w},{d['acc']:.4f},{s['acc']:.4f},"
              f"{s['acc']-d['acc']:+.4f},{d['ms_per_step']:.3f},"
              f"{s['ms_per_step']:.3f},{speed:.2f}x")
        emit(f"table1/width{w}/dense", d["ms_per_step"] * 1e3,
             f"acc={d['acc']:.4f}")
        emit(f"table1/width{w}/spm", s["ms_per_step"] * 1e3,
             f"acc={s['acc']:.4f}")


if __name__ == "__main__":
    main()
