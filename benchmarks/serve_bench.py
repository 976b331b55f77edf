"""Continuous-batching serving benchmark -> ``BENCH_serve.json``.

Drives ``serve.ContinuousBatchingEngine`` with a seeded Poisson arrival
process at several offered loads (requests per decode tick) and reports,
per load: total decode ticks, slot occupancy, and p50/p99 per-request
latency in TICKS (arrival -> final token), plus wall-clock tokens/sec.

The regression gate (``check_regression.py --serve-baseline``) consumes
only the SCHEDULE-DETERMINISTIC numbers — ticks, tokens, occupancy,
latency percentiles, and the single-compile count of the decode tick.
Those depend on the seeded arrivals and the admit/evict policy, never on
model weights or sampled token values (eviction triggers on token COUNT),
so they reproduce bit-for-bit across machines.  Wall-clock (``wall_s``,
``tokens_per_s``) is recorded for the trajectory but never gated: off-TPU
it is XLA-CPU noise, not a hardware claim.

  PYTHONPATH=src:. python benchmarks/serve_bench.py --smoke \
      --out BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.analysis.recompile import CompileTracker
from repro.configs import get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import ContinuousBatchingEngine, Request

DEFAULT_LOADS = (0.2, 0.5, 2.0)   # requests per decode tick
PROMPT_LENS = (5, 12, 24, 7)      # cycled per request: mixes buckets
SCHEMA = "serve_bench/v1"


def poisson_arrivals(n: int, rate: float, seed: int) -> list:
    """Arrival tick (int) per request: cumulative exponential
    inter-arrival gaps at ``rate`` requests/tick, seeded — deterministic
    for the gate."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(scale=1.0 / rate, size=n)
    return [int(t) for t in np.floor(np.cumsum(gaps))]


def percentile_ticks(lat: list, q: float) -> int:
    """Nearest-rank percentile over integer tick latencies (deterministic,
    no interpolation)."""
    s = sorted(lat)
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return int(s[idx])


def run_load(eng: ContinuousBatchingEngine, load: float, n_requests: int,
             max_new: int, vocab: int, seed: int) -> dict:
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n_requests):
        plen = PROMPT_LENS[i % len(PROMPT_LENS)]
        prompt = jax.random.randint(jax.random.fold_in(key, i),
                                    (plen,), 0, vocab)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new, rid=i))
    arrivals = poisson_arrivals(n_requests, load, seed)
    t0 = time.perf_counter()
    results, stats = eng.serve(reqs, arrival_ticks=arrivals)
    wall = time.perf_counter() - t0
    lat = [results[i]["finished_tick"] - arrivals[i]
           for i in range(n_requests)]
    occ = stats["occupied_slot_ticks"] * 1000 \
        // max(stats["ticks"] * eng.slots, 1)
    return {
        "offered_load": load,
        "ticks": stats["ticks"],
        "tokens": stats["tokens"],
        "occupancy_milli": int(occ),
        "p50_latency_ticks": percentile_ticks(lat, 0.50),
        "p99_latency_ticks": percentile_ticks(lat, 0.99),
        # wall-clock: reported, never gated
        "wall_s": round(wall, 3),
        "tokens_per_s": round(stats["tokens"] / wall, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-scale config (the committed-baseline scale)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loads", type=float, nargs="+",
                    default=list(DEFAULT_LOADS))
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if not args.smoke:
        print("note: full-scale serve bench off-TPU is slow; the gate "
              "runs --smoke")
    cfg = get_smoke(args.arch)
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    eng = ContinuousBatchingEngine(cfg, params, slots=args.slots,
                                   max_len=args.max_len,
                                   base_key=jax.random.PRNGKey(args.seed))

    # warm the tick on a single throwaway request so the per-load loop —
    # and the compile sentinel — measure the steady state
    warm = Request(prompt=jax.numpy.zeros((4,), jax.numpy.int32),
                   max_new_tokens=2, rid=10**9)
    eng.serve([warm])
    with CompileTracker(tick=eng._tick) as tracker:
        loads = [run_load(eng, load, args.requests, args.max_new,
                          cfg.vocab_size, args.seed)
                 for load in sorted(args.loads)]
    tick_compiles = tracker.new_compiles()["tick"]

    payload = {
        "schema": SCHEMA,
        "arch": cfg.name,
        "backend": jax.default_backend(),
        "slots": args.slots,
        "requests": args.requests,
        "max_new": args.max_new,
        # steady-state compile count of the decode tick across EVERY load:
        # 0 new entries after warmup == one compiled tick serves all churn
        "tick_compiles": tick_compiles,
        "loads": loads,
    }
    out = args.out
    if not os.path.isabs(out):
        out = os.path.join(os.getcwd(), out)
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    for row in loads:
        print(f"load={row['offered_load']:<4} ticks={row['ticks']:<4} "
              f"occ={row['occupancy_milli']/10:.0f}% "
              f"p50={row['p50_latency_ticks']} "
              f"p99={row['p99_latency_ticks']} "
              f"({row['tokens_per_s']} tok/s wall)")
    print(f"tick compiles after warmup: {tick_compiles} -> {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
