"""Plain reference of the optimizer a training cell states: AdamW
(decoupled weight decay on parameter arrays of rank 2 or more as the
tree stores them), global-norm clipping, linear warm-up then cosine decay
to ``min_lr_frac``.  Everything in float32."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def learning_rate(opt: dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def init_state(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(z, params), "nu": jax.tree.map(z, params)}


def step(opt: dict, params, grads, state, count: int):
    """One update (``count`` is 1 for the first).  Returns the new params,
    state and the clipped gradient the moments took in."""
    g = clip(grads, opt["clip_norm"])
    lr = learning_rate(opt, count)
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(p, g, mu, nu):
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        u = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
        if p.ndim >= 2:
            u = u + wd * p
        return p - lr * u, mu, nu

    out = jax.tree.map(upd, params, g, state["mu"], state["nu"])
    leaves, treedef = jax.tree.flatten(params)
    flat = treedef.flatten_up_to(out)
    new_p = treedef.unflatten([o[0] for o in flat])
    new_s = {"mu": treedef.unflatten([o[1] for o in flat]),
             "nu": treedef.unflatten([o[2] for o in flat])}
    return new_p, new_s, g
