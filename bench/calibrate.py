#!/usr/bin/env python3
"""Readings that the correctness limits are set from (run on the chip at
the cell's own size; the benchmark's runs never call this).

    python3 bench/calibrate.py --workload <cell> --seeds 101,102,... \\
        --control-seeds 101,102,103 [--seconds 8]

For each seed it prints one JSON line with the program's numbers (as a
run computes them).  For each control seed it also prints the numbers of
the control — the plain reference computed in the next precision below
the configuration's (``fp8`` activations for bf16) put in the program's
place — and of the planted faults the cell can have: a training batch
with half its rows left out (the mean over the rest), or a served token
altered where it is produced.  A step that returns its state unchanged
reads 1 on ``change_gap`` by definition and needs no run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec  # noqa: E402

CONTROL = "fp8"


def say(**kw):
    print(json.dumps(kw), flush=True)


def train(cell, seeds, control_seeds):
    drv = spec.kind_module(cell)
    step_fn = None
    for seed in seeds:
        t = time.time()
        prog = drv.Program(cell, seed)
        if step_fn is not None:
            prog.step_fn = prog.call = step_fn
        readings = prog.check_steps(cell.traffic["check_steps"])
        step_fn = prog.step_fn
        prog.state = None
        del prog
        gc.collect()
        ref = drv.reference_readings(cell, seed)
        nums, notes = drv.compare(readings, ref)
        say(seed=seed, side="program", numbers=nums, notes=notes,
            seconds=round(time.time() - t, 1))
        if seed in control_seeds:
            ctl = drv.reference_readings(cell, seed, CONTROL)
            say(seed=seed, side="control", numbers=drv.compare(ctl, ref)[0])
            hb = drv.reference_readings(cell, seed, half_batch=True)
            say(seed=seed, side="fault_half_batch",
                numbers=drv.compare(hb, ref)[0])


def serve(cell, seeds, control_seeds, seconds):
    import numpy as np
    drv = spec.kind_module(cell)
    srv = None
    for seed in seeds:
        t = time.time()
        if srv is None:
            srv = drv.Server(cell, seed, harness.span)
        else:
            srv.reseed(seed)
        srv.probe.faults = {}
        w = srv.window(seconds)
        prec = ("f32", CONTROL) if seed in control_seeds else ("f32",)
        rd = drv.reference_readings(cell, w["sample"], w["served"],
                                    w["first_logits"], prec,
                                    params=srv.params)
        say(seed=seed, side="program", numbers=drv.numbers_of(rd["f32"]),
            tokens=int(rd["f32"]["gap"].size), requests=len(w["sample"]),
            ttft_p95_s=drv.percentile(w["ttft"], 95),
            seconds=round(time.time() - t, 1))
        if seed in control_seeds:
            say(seed=seed, side="control",
                numbers=drv.numbers_of(rd[CONTROL]),
                gap_quantiles=[float(np.quantile(rd[CONTROL]["gap"], q))
                               for q in (0.5, 0.9, 0.99)])
            srv.probe.faults = {"alter_token": True}
            w = srv.window(seconds)
            rd = drv.reference_readings(cell, w["sample"], w["served"],
                                        w["first_logits"],
                                        params=srv.params)
            say(seed=seed, side="fault_alter_token",
                numbers=drv.numbers_of(rd["f32"]))
            srv.probe.faults = {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    code = harness.require_chips(cell.chips)
    if code:
        return code
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    if cell.kind == "train":
        train(cell, seeds, ctl)
    else:
        serve(cell, seeds, ctl, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
