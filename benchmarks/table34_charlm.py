"""Paper Tables 3–4: char-level LM with one wide projection (d=4096).

Model mirrors the paper's §9.3 setup: token embedding -> ONE wide linear
projection of dimension d (dense vs SPM butterfly L=12) -> ReLU -> tied
head; T=128, B=32, lr=1e-3.  The corpus is a synthesized Bard proxy
(data/char_corpus.py, SIMULATED).  Reports NLL/BPC trajectory + ms/step.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.paper import CHARLM_B, CHARLM_D, CHARLM_L, CHARLM_LR, CHARLM_T
from repro.core.linear import LinearConfig, init_linear, linear_apply
from repro.data import build_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import OptimizerConfig
from repro.train import make_train_state, make_train_step

KEY = jax.random.PRNGKey(0)
VOCAB = 256


@dataclasses.dataclass(frozen=True)
class CharLMCfg:
    d: int
    impl: str
    n_stages: int = CHARLM_L

    @property
    def proj(self) -> LinearConfig:
        return LinearConfig(d_in=self.d, d_out=self.d, impl=self.impl,
                            n_stages=self.n_stages, schedule="butterfly",
                            backward="custom")


def init_charlm(cfg: CharLMCfg) -> dict:
    k1, k2 = jax.random.split(KEY)
    return {"embed": 0.02 * jax.random.normal(k1, (VOCAB, cfg.d)),
            "proj": init_linear(k2, cfg.proj)}


def charlm_loss(params, batch, cfg: CharLMCfg):
    h = params["embed"][batch["tokens"]]
    h = jax.nn.relu(linear_apply(params["proj"], h, cfg.proj))
    logits = h @ params["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "nll": loss, "bpc": loss / jnp.log(2.0)}


def run_one(d: int, impl: str, steps: int, eval_every: int,
            corpus: np.ndarray, batch: int, seq: int):
    cfg = CharLMCfg(d=d, impl=impl)
    state = make_train_state(init_charlm(cfg))
    step = jax.jit(make_train_step(
        lambda p, b: charlm_loss(p, b, cfg),
        OptimizerConfig(lr=CHARLM_LR, total_steps=steps, warmup_steps=0)))
    rng = np.random.default_rng(0)
    split = int(0.9 * len(corpus))
    train_c, valid_c = corpus[:split], corpus[split:]

    def draw(c):
        starts = rng.integers(0, len(c) - seq - 1, size=batch)
        idx = starts[:, None] + np.arange(seq + 1)[None, :]
        ch = c[idx]
        return {"tokens": jnp.asarray(ch[:, :-1], jnp.int32),
                "labels": jnp.asarray(ch[:, 1:], jnp.int32)}

    rows, t_total = [], 0.0
    for s in range(1, steps + 1):
        b = draw(train_c)
        t0 = time.perf_counter()
        state, m = step(state, b)
        jax.block_until_ready(m["loss"])
        t_total += time.perf_counter() - t0
        if s == 1 or s % eval_every == 0:
            vl = np.mean([float(charlm_loss(state["params"], draw(valid_c),
                                            cfg)[0]) for _ in range(3)])
            rows.append((s, float(m["loss"]), vl, vl / np.log(2),
                         t_total / s * 1e3))
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help=f"paper scale d={CHARLM_D} (slow on 1-core CPU)")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    d = CHARLM_D if args.full else 1024
    steps = args.steps or (800 if args.full else 60)
    eval_every = max(steps // 5, 1)
    batch, seq = (CHARLM_B, CHARLM_T) if args.full else (16, 64)
    corpus = build_corpus(1_100_000 if args.full else 200_000)

    print(f"# Tables 3-4 repro: char-LM d={d} L={CHARLM_L} (SIMULATED corpus)")
    for impl in ("dense", "spm_general"):
        rows = run_one(d, impl, steps, eval_every, corpus, batch, seq)
        print(f"## {impl}")
        print("step,train_nll,valid_nll,valid_bpc,ms_per_step")
        for r in rows:
            print(f"{r[0]},{r[1]:.3f},{r[2]:.3f},{r[3]:.3f},{r[4]:.1f}")
        emit(f"table34/{impl}/d{d}", rows[-1][4] * 1e3,
             f"valid_bpc={rows[-1][3]:.3f}")


if __name__ == "__main__":
    main()
