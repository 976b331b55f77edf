"""Paper Table 2: hashed-sparse text classification (AG News proxy).

Dense vs SPM at fixed stage depth L=12, width sweep.  The corpus is
SIMULATED (class-conditional hashed features — data/hashed_text.py);
the tested CLAIM is the paper's: at large width SPM trains several times
faster per step while matching/exceeding dense accuracy.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import emit, time_step
from repro.configs.paper import AGNEWS_CLASSES, AGNEWS_L, student_cfg
from repro.data import DeterministicLoader
from repro.data.hashed_text import HashedTextConfig, hashed_text_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_mlp, mlp_loss
from repro.optim import OptimizerConfig
from repro.train import make_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def run_one(width: int, impl: str, steps: int, batch: int) -> dict:
    hc = HashedTextConfig(width=width, n_classes=AGNEWS_CLASSES)
    loader = DeterministicLoader(
        lambda k, n: hashed_text_batch(hc, k, n), batch, seed=0)
    cfg = student_cfg(width, AGNEWS_CLASSES, impl, n_stages=AGNEWS_L)
    state = make_train_state(init_mlp(KEY, cfg))
    step = jax.jit(make_train_step(
        lambda p, b: mlp_loss(p, b, cfg),
        OptimizerConfig(lr=3e-3, total_steps=steps)))
    ms = time_step(lambda s, b: step(s, b)[0], state, loader.batch_at(0)) * 1e3
    for s in range(steps):
        state, _ = step(state, loader.batch_at(s))
    accs = []
    for s in range(10_000, 10_005):
        _, m = mlp_loss(state["params"], loader.batch_at(s), cfg)
        accs.append(float(m["acc"]))
    return {"acc": float(np.mean(accs)), "ms_per_step": ms}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()
    widths = (2048, 4096) if args.full else (512, 1024)
    steps = 800 if args.full else 200
    batch = 256 if args.full else 128

    print(f"# Table 2 repro: hashed sparse text (L={AGNEWS_L}, SIMULATED)")
    print("width,dense_acc,spm_acc,delta_acc,dense_ms,spm_ms,speedup")
    for w in widths:
        d = run_one(w, "dense", steps, batch)
        s = run_one(w, "spm_general", steps, batch)
        speed = d["ms_per_step"] / max(s["ms_per_step"], 1e-9)
        print(f"{w},{d['acc']:.4f},{s['acc']:.4f},"
              f"{s['acc']-d['acc']:+.4f},{d['ms_per_step']:.3f},"
              f"{s['ms_per_step']:.3f},{speed:.2f}x")
        emit(f"table2/width{w}/dense", d["ms_per_step"] * 1e3,
             f"acc={d['acc']:.4f}")
        emit(f"table2/width{w}/spm", s["ms_per_step"] * 1e3,
             f"acc={s['acc']:.4f}")


if __name__ == "__main__":
    main()
