"""Find the tick-time knee of a serving mix: the highest arrival rate, in
requests per engine tick, that the engine sustains without a growing
backlog.

Arrivals are in engine ticks and admission depends only on lengths and
slots, so the schedule is the same at any model size or device speed:
the sweep runs the program's ``ContinuousBatchingEngine`` with the
registry's small ("smoke") configuration on the CPU, its model calls
stubbed after their first call at each shape (``kinds/serve.py``'s
set-up mode), so only the engine's scheduling runs.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/knee_sweep.py \\
        --traffic serve-prefill --arch qwen3-32b --rates 0.15:0.32:0.01

A rate counts as sustained when the mean queueing wait (admitted tick
minus arrival tick) does not grow with the length of the run: over
``--requests`` it stays within 1.3x (plus 2 ticks) of that over a third
as many.  A stable queue's wait does not depend on the run's length; a
queue past capacity grows with it.  Each rate is averaged over the
schedules of ``--seeds`` (each the mix's ``schedule_seed``).  Prints one
JSON line per rate and a last line with the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import numpy as np  # noqa: E402

import traffic  # noqa: E402


def mean_wait(eng, mix: dict, vocab: int, rate: float, n: int,
              seed: int) -> float:
    from repro.serve.engine import Request
    mix = dict(mix, requests=n, schedule_seed=seed,
               arrivals=dict(mix["arrivals"], rate_per_tick=rate))
    reqs, arrivals = traffic.serve_requests(mix, vocab, seed)
    prog = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p, rid=r.rid) for r in reqs]
    results, _ = eng.serve(prog, arrival_ticks=arrivals)
    return float(np.mean([results[r.rid]["admitted_tick"] - arrivals[r.rid]
                          for r in reqs]))


def sweep_rate(eng, mix, vocab, rate, n, seeds) -> dict:
    short = np.mean([mean_wait(eng, mix, vocab, rate, n // 3, s)
                     for s in seeds])
    long = np.mean([mean_wait(eng, mix, vocab, rate, n, s) for s in seeds])
    return {"rate_per_tick": rate, "mean_wait_ticks_short": float(short),
            "mean_wait_ticks_long": float(long),
            "sustained": bool(long <= 1.3 * short + 2.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--rates", default="0.15:0.32:0.01")
    ap.add_argument("--requests", type=int, default=3000)
    ap.add_argument("--seeds", default="0,1,2")
    args = ap.parse_args(argv)
    import jax

    import harness
    import spec
    from repro.configs import get_smoke
    from repro.models import transformer as T
    from repro.serve.engine import ContinuousBatchingEngine
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           f"{args.traffic}.json")) as f:
        mix = json.load(f)
    cfg = get_smoke(args.arch)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params, slots=mix["slots"],
                                   max_len=mix["max_len"])
    serve = spec.load_module(os.path.join(os.path.dirname(HERE), "kinds",
                                          "serve.py"))
    probe = serve.EngineProbe(eng, harness.span)
    probe.stub = True
    seeds = [int(x) for x in args.seeds.split(",")]
    lo, hi, step = (float(x) for x in args.rates.split(":"))
    knee = None
    for rate in np.arange(lo, hi + 1e-9, step):
        row = sweep_rate(eng, mix, cfg.vocab_size, round(float(rate), 4),
                         args.requests, seeds)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = row["rate_per_tick"]
        else:
            break
    print(json.dumps({"knee_per_tick": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
