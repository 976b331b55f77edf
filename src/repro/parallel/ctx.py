"""Activation-sharding context: explicit constraints inside model code.

SPM models have no large matmuls, so XLA's sharding propagation cannot
discover head/feature parallelism on its own (DESIGN.md §3.4, EXPERIMENTS
§Perf).  Layers call ``constrain(x, kind)`` at strategic points; outside
any context this is the identity, so CPU smoke paths and the naive
baseline are untouched.

Kinds:
  "heads":      (B, T, H, dh)   -> heads over "model", batch over DP axes
  "kv_heads":   (B, T, Hkv, dh) -> same on the KV head axis
  "btd":        (B, T, D)       -> batch over DP axes, feature replicated
  "batch_full": (B, ...)        -> batch over DP axes + "model" (full-mesh
                                   DP — the spm_dp training layout)
  "feature":    (..., n)        -> feature over "model" (two-level SPM)
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["activation_sharding", "constrain", "feature_mesh",
           "sharding_active"]

_STATE = threading.local()


def _current() -> Optional[dict]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, *, shard_heads: bool = True,
                        shard_feature: bool = False,
                        full_batch: bool = False):
    """Enable explicit activation constraints within the block."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    prev = _current()
    _STATE.ctx = {"mesh": mesh, "dp": dp, "shard_heads": shard_heads,
                  "shard_feature": shard_feature, "full_batch": full_batch}
    try:
        yield
    finally:
        _STATE.ctx = prev


def sharding_active() -> bool:
    """Whether an ``activation_sharding`` block is open."""
    return _current() is not None


def feature_mesh(n_shards: Optional[int] = None) -> Optional[Mesh]:
    """The active mesh when feature sharding is enabled, else None.

    ``core/spm.spm_apply`` calls this to decide whether to route a
    two_level operator through the distributed executor
    (``parallel/spm_shard.py``): it needs an ``activation_sharding`` block
    with ``shard_feature=True``, a ``"model"`` mesh axis, and (when
    ``n_shards`` is given) an axis size matching the operator's shard
    count — otherwise the unsharded composition runs and XLA partitions it.
    """
    ctx = _current()
    if ctx is None or not ctx.get("shard_feature"):
        return None
    mesh = ctx["mesh"]
    if "model" not in mesh.axis_names:
        return None
    if n_shards is not None and mesh.shape["model"] != n_shards:
        return None
    return mesh


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Apply the activation-sharding constraint of ``kind`` (see the
    module docstring) under the active ``activation_sharding`` context;
    the identity when no context is active."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, dp = ctx["mesh"], ctx["dp"]
    if kind in ("heads", "kv_heads"):
        if not ctx["shard_heads"]:
            return x
        spec = P(dp, None, "model", None)
    elif kind == "btd":
        spec = P(dp, *([None] * (x.ndim - 1)))
    elif kind == "batch_full":
        if not ctx.get("full_batch"):
            return x
        spec = P(dp + ("model",), *([None] * (x.ndim - 1)))
    elif kind == "feature":
        if not ctx["shard_feature"]:
            return x
        spec = P(*([None] * (x.ndim - 1)), "model")
    else:
        raise ValueError(kind)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
