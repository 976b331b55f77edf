"""AdamW + cosine schedule + global-norm clipping (pure JAX, no optax)."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs

__all__ = ["OptimizerConfig", "init_opt_state", "adamw_update",
           "cosine_schedule", "global_norm", "clip_by_global_norm"]


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def cosine_schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1.0 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree: Any, max_norm: float
                        ) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale
                                   ).astype(x.dtype), tree), norm


def init_opt_state(params: Any) -> dict:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "count": jnp.zeros((), jnp.int32)}


@obs.scoped("optimizer")
def adamw_update(params: Any, grads: Any, state: dict,
                 cfg: OptimizerConfig,
                 lr: Optional[jax.Array] = None) -> Tuple[Any, dict, dict]:
    """Returns (new_params, new_state, info)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    count = state["count"] + 1
    if lr is None:
        lr = cosine_schedule(cfg, count)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1.0 - b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32)
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        step = (mu / bc1) / (jnp.sqrt(nu / bc2) + cfg.eps)
        if p.ndim >= 2:   # decoupled weight decay on matrices only
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), mu, nu

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n
           in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_mu = treedef.unflatten([o[1] for o in out])
    new_nu = treedef.unflatten([o[2] for o in out])
    # extra optimizer-state keys (e.g. the error-feedback residual "ef"
    # carried by the compressed-psum train step) pass through untouched —
    # their owner updates them, AdamW only owns mu/nu/count
    new_state = {**state, "mu": new_mu, "nu": new_nu, "count": count}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
