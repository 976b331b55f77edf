"""The numbers that decide ``correct``, and how each is held to its
limit.

* ``rel_gap(p, r)`` — ``|p - r| / |r|``.
* ``worst_leaf_gap(p, r)`` — per leaf, the gap between the program's norm
  and the reference's, over the larger of the reference's norm of that
  leaf and of the median leaf; the worst leaf's gap.
* ``served_gap`` — how far a served token's reference logit lies below
  the reference's best at that position.

Limits live in ``limits/<workload>.json`` (``spec.load_cell`` reads
them); a number without a limit fails.
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, Optional, Tuple

import numpy as np


def rel_gap(p: float, r: float) -> float:
    return abs(p - r) / max(abs(r), 1e-30)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Iterable[str]] = None
                   ) -> Tuple[float, str]:
    names = list(ref if keep is None else keep)
    med = statistics.median(ref[k] for k in ref)
    worst, which = 0.0, ""
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g >= worst:
            worst, which = g, k
    return worst, which


def moving_leaves(ref_grad_norms: Dict[str, float],
                  frac: float = 1e-3) -> list:
    """Leaves whose reference gradient is above ``frac`` of the median
    leaf's; the others move under Adam by round-off alone."""
    med = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= frac * med]


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of
    the token served there."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - got


def judge(numbers: Dict[str, float], limits: dict) -> Tuple[bool, dict]:
    """``correct`` and the ``checks`` entry of the result line: each number
    beside its limit (a missing limit, or a number that is not finite,
    fails)."""
    out, ok = {}, True
    for name, v in numbers.items():
        lim = limits.get(name, {}).get("limit")
        passed = (lim is not None and v is not None and np.isfinite(v)
                  and v <= lim)
        ok &= bool(passed)
        out[name] = {"value": v, "limit": lim}
    return ok, out
