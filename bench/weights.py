"""Seeded weights, made on the device in one jitted call.

The tree's structure and shapes are the program's (``jax.eval_shape`` of
its initializer); every value is drawn here, by leaf name, so neither the
program nor its reference supplies the other's weights:

* ``mix`` (SPM coefficients, ``(..., L, n/2, 4)``): a random rotation
  ``(cos t, -sin t, sin t, cos t)`` per pair plus 0.05 normal noise;
* ``d_in``, ``d_out``, ``scale``, ``q_norm``, ``k_norm``: 1 + 0.1 normal;
* ``table``, ``out`` (embedding, untied head), ``router`` (a mixture of
  experts' dense router): 0.02 normal.

An unknown leaf is an error: the reference would not know it either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ONE_PLUS = ("d_in", "d_out", "scale", "q_norm", "k_norm")
NORMAL_002 = ("table", "out", "router")


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _leaf(key, name: str, shape, dt):
    if name == "mix":
        kt, kn = jax.random.split(key)
        th = jax.random.uniform(kt, shape[:-1], jnp.float32, -jnp.pi,
                                jnp.pi)
        c, s = jnp.cos(th), jnp.sin(th)
        rot = jnp.stack([c, -s, s, c], axis=-1)
        return (rot + 0.05 * jax.random.normal(kn, shape)).astype(dt)
    if name in ONE_PLUS:
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dt)
    if name in NORMAL_002:
        return (0.02 * jax.random.normal(key, shape)).astype(dt)
    raise KeyError(f"no weight rule for parameter leaf {name!r}")


@functools.lru_cache(maxsize=None)
def _generator(names, shapes):
    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(keys[i], names[i], *shapes[i])
                for i in range(len(names))]
    return gen


def make_params(structure, seed32: int):
    """Weights shaped like ``structure`` (a tree of ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(structure)
    names = tuple(leaf_name(p) for p, _ in flat)
    shapes = tuple((tuple(s.shape), jnp.dtype(s.dtype)) for _, s in flat)
    leaves = _generator(names, shapes)(jax.random.PRNGKey(seed32))
    return jax.tree_util.tree_unflatten(treedef, leaves)
