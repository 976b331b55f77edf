"""Token embedding and output head.

Both stay DENSE regardless of ``linear_impl``: vocab tables are lookup /
classification maps over a categorical axis, not square feature mixers —
SPM's pairwise-mixing inductive bias does not apply (DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs

__all__ = ["EmbeddingConfig", "init_embedding", "embed", "unembed"]


@dataclasses.dataclass(frozen=True)
class EmbeddingConfig:
    vocab_size: int
    d_model: int
    tie_output: bool = True
    param_dtype: Any = jnp.float32


def init_embedding(key: jax.Array, cfg: EmbeddingConfig) -> dict:
    ke, ko = jax.random.split(key)
    p = {"table": 0.02 * jax.random.normal(
        ke, (cfg.vocab_size, cfg.d_model), cfg.param_dtype)}
    if not cfg.tie_output:
        p["out"] = 0.02 * jax.random.normal(
            ko, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
    return p


@obs.scoped("embed")
def embed(params: dict, tokens: jax.Array, cfg: EmbeddingConfig,
          dtype=jnp.float32, onehot: bool = False) -> jax.Array:
    """Token lookup.  ``onehot=True`` lowers as a matmul: with the table
    vocab-sharded over "model" this becomes a sharded contraction + one
    small all-reduce of (tokens, d) partial sums — instead of the
    replicate-the-table gather XLA's SPMD falls back to (EXPERIMENTS
    §Perf iteration 1)."""
    if onehot:
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=dtype)
        return oh @ params["table"].astype(dtype)
    return params["table"].astype(dtype)[tokens]


def unembed(params: dict, h: jax.Array, cfg: EmbeddingConfig) -> jax.Array:
    if cfg.tie_output:
        return h @ params["table"].astype(h.dtype).T
    return h @ params["out"].astype(h.dtype)
