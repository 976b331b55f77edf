"""Batched serving driver (smoke-scale on CPU; production mesh on TPU).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 16

``--continuous`` switches to the continuous-batching engine: the same
requests run through a churning admit/evict pool over ``--slots``
compiled batch rows (staggered arrivals, per-request sampling params),
reporting tokens/sec, slot occupancy, and per-request latency in ticks.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke, with_overrides
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import ContinuousBatchingEngine, Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--linear-impl", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dtype", choices=("bfloat16", "float32"),
                    default="bfloat16",
                    help="KV-cache dtype (default matches the engine's "
                         "bf16 default; float32 for parity debugging)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine: admit/evict the "
                         "requests through a fixed-slot decode tick")
    ap.add_argument("--slots", type=int, default=4,
                    help="compiled batch slots (continuous mode)")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="ticks between request arrivals (continuous mode)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.linear_impl:
        cfg = with_overrides(cfg, linear_impl=args.linear_impl)
    if cfg.input_kind != "tokens":
        print(f"note: {cfg.name} is embeddings-input; serving decodes its "
              f"token codebook after a token prompt")

    # independent streams for init / prompts / sampling: reusing one key
    # correlates the model weights with the benchmark prompts and the
    # sampling noise
    k_init, k_prompts, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 3)
    params = T.init_model(k_init, cfg)

    if args.continuous:
        eng = ContinuousBatchingEngine(
            cfg, params, slots=args.slots,
            max_len=args.prompt_len + args.new_tokens,
            cache_dtype=jnp.dtype(args.cache_dtype),
            base_key=k_sample)
        reqs = [Request(prompt=jax.random.randint(
                            jax.random.fold_in(k_prompts, i),
                            (args.prompt_len,), 0, cfg.vocab_size),
                        max_new_tokens=args.new_tokens,
                        temperature=args.temperature, rid=i)
                for i in range(args.batch)]
        arrivals = [i * args.arrival_every for i in range(args.batch)]
        t0 = time.time()
        results, stats = eng.serve(reqs, arrival_ticks=arrivals)
        dt = time.time() - t0
        occ = stats["occupied_slot_ticks"] / max(stats["ticks"]
                                                 * args.slots, 1)
        lat = [results[r.rid]["finished_tick"]
               - results[r.rid]["admitted_tick"] for r in reqs]
        print(f"served {len(reqs)} requests / {stats['tokens']} tokens in "
              f"{stats['ticks']} ticks, {dt:.2f}s "
              f"({stats['tokens']/dt:.1f} tok/s, occupancy {occ:.2f}, "
              f"latency {min(lat)}-{max(lat)} ticks)")
        for r in reqs:
            print(r.rid, results[r.rid]["tokens"])
        return

    engine = ServeEngine(cfg=cfg, params=params,
                         max_len=args.prompt_len + args.new_tokens,
                         cache_dtype=jnp.dtype(args.cache_dtype))
    prompts = jax.random.randint(k_prompts, (args.batch, args.prompt_len),
                                 0, cfg.vocab_size)
    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                          temperature=args.temperature, key=k_sample)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s batch-aggregate)")
    print(out)


if __name__ == "__main__":
    main()
