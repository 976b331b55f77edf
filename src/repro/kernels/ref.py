"""Pure-jnp oracle for the fused SPM stage-stack kernel.

Semantics shared with ``kernels/spm_stack.py``: apply L structured
(stride-pairing) mixing stages to the last axis of ``x``.

    z_0 = x;   z_l = B_l z_{l-1};   return z_L

``coeffs`` is (L, n//2, 4) holding (a, b, c, d) per pair; ``strides`` is a
static tuple of per-stage strides with ``n % (2*s) == 0``.

This module is the correctness reference: tests assert the Pallas kernel
(interpret mode on CPU) matches ``spm_stack_ref`` across shape/dtype sweeps.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

__all__ = ["spm_stack_ref", "spm_stack_grads_ref", "spm_full_ref",
           "spm_runs_ref"]


def _stage(z, cf, s):
    """One stride-s stage.  z: (..., n); cf: (n//2, 4)."""
    n = z.shape[-1]
    lead = z.shape[:-1]
    g = n // (2 * s)
    zr = z.reshape(lead + (g, 2, s))
    x0, x1 = zr[..., 0, :], zr[..., 1, :]
    a, b, c, d = (cf[:, i].reshape(g, s) for i in range(4))
    y0 = a * x0 + b * x1
    y1 = c * x0 + d * x1
    return jnp.stack([y0, y1], axis=-2).reshape(lead + (n,))


def spm_stack_ref(x: jnp.ndarray, coeffs: jnp.ndarray,
                  strides: Tuple[int, ...]) -> jnp.ndarray:
    z = x
    for ell, s in enumerate(strides):
        z = _stage(z, coeffs[ell].astype(z.dtype), s)
    return z


def spm_full_ref(x: jnp.ndarray, coeffs: jnp.ndarray,
                 strides: Tuple[int, ...],
                 d_in: Optional[jnp.ndarray] = None,
                 d_out: Optional[jnp.ndarray] = None,
                 bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Oracle for the FULL operator y = D_out (B_L...B_1) D_in x + bias,
    matching the diag/bias folding of the fused kernel path."""
    z = x if d_in is None else x * d_in.astype(x.dtype)
    z = spm_stack_ref(z, coeffs, strides)
    if d_out is not None:
        z = z * d_out.astype(z.dtype)
    if bias is not None:
        z = z + bias.astype(z.dtype)
    return z


def spm_runs_ref(x: jnp.ndarray, coeffs: jnp.ndarray,
                 runs: Sequence[Tuple[int, ...]], io_dtype,
                 d_in: Optional[jnp.ndarray] = None,
                 d_out: Optional[jnp.ndarray] = None,
                 bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``spm_full_ref`` with the stage stack split into ``runs`` (the
    stride tuples of a run plan, e.g. from ``ops.plan_runs``) and the
    activation stored in ``io_dtype`` between runs, as the fused path
    hands one kernel's output to the next.  Autodiff through that cast
    rounds the cotangent to ``io_dtype`` at the same boundaries, as the
    fused backward stores each run's input cotangent.  With an f32
    ``io_dtype`` this is ``spm_full_ref``.

    The rounding is ``lax.reduce_precision``, not a cast round trip: XLA
    on TPU may drop a ``f32 -> bf16 -> f32`` convert pair as excess
    precision, which would leave this oracle unrounded."""
    fi = jnp.finfo(io_dtype)
    z = x if d_in is None else x * d_in.astype(x.dtype)
    off = 0
    for r, strides in enumerate(runs):
        if r and fi.bits < jnp.finfo(x.dtype).bits:
            z = lax.reduce_precision(z, exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
        z = spm_stack_ref(z, coeffs[off: off + len(strides)], strides)
        off += len(strides)
    if d_out is not None:
        z = z * d_out.astype(z.dtype)
    if bias is not None:
        z = z + bias.astype(z.dtype)
    return z


def spm_stack_grads_ref(x, coeffs, strides, gy):
    """Closed-form (paper §4.2) backward for the stage stack.

    Returns (g_x, g_coeffs).  Used to validate the kernel-wrapped custom_vjp.
    """
    # forward, collecting stage inputs
    zs = []
    z = x
    for ell, s in enumerate(strides):
        zs.append(z)
        z = _stage(z, coeffs[ell].astype(z.dtype), s)
    g_coeffs = []
    delta = gy
    n = x.shape[-1]
    lead = x.shape[:-1]
    bdims = tuple(range(len(lead)))
    for ell in range(len(strides) - 1, -1, -1):
        s = strides[ell]
        g = n // (2 * s)
        cf = coeffs[ell].astype(delta.dtype)
        a, b, c, d = (cf[:, i].reshape(g, s) for i in range(4))
        zr = zs[ell].reshape(lead + (g, 2, s))
        dr = delta.reshape(lead + (g, 2, s))
        x0, x1 = zr[..., 0, :], zr[..., 1, :]
        d0, d1 = dr[..., 0, :], dr[..., 1, :]
        ga = jnp.sum(d0 * x0, axis=bdims).reshape(-1)
        gb = jnp.sum(d0 * x1, axis=bdims).reshape(-1)
        gc = jnp.sum(d1 * x0, axis=bdims).reshape(-1)
        gd = jnp.sum(d1 * x1, axis=bdims).reshape(-1)
        g_coeffs.append(jnp.stack([ga, gb, gc, gd], axis=-1))
        gx0 = a * d0 + c * d1
        gx1 = b * d0 + d * d1
        delta = jnp.stack([gx0, gx1], axis=-2).reshape(lead + (n,))
    return delta, jnp.stack(g_coeffs[::-1], axis=0)
