#!/usr/bin/env python3
"""Run one benchmark cell on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix, metrics and correctness limits are
found by name (``spec.py``).  The run checks for the chips the cell asks
for, makes weights and inputs from ``--seed``, warms every shape the
traffic uses (set-up), measures for ``--seconds``, then compares what the
timed path produced with the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared, beside its limit.  Those also end standard error.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    import repro  # noqa: F401  (fails here when the program is absent)
    code = harness.require_chips(cell.chips)
    if code:
        return code
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = harness.run(cell, args, t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
