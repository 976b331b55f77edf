"""Train/eval step factories.

``make_train_step(loss_fn, opt_cfg, ...)`` returns a jittable
``step(state, batch) -> (state, metrics)`` with:

  * optional microbatch gradient accumulation (``accum_steps`` splits the
    per-device batch along axis 0 and ``lax.scan``s the grads — constant
    memory in global batch; metrics are averaged across microbatches,
    mask-weighted for ``ce`` via ``ce_weight``, so logs describe the same
    batch the loss optimizes),
  * global-norm clipping + AdamW + cosine schedule,
  * a NaN/inf GUARD: if the gradient global-norm is non-finite the update
    is skipped entirely (params and opt state pass through) and
    ``metrics["skipped"]`` flags it — the fault-tolerance layer counts
    these (train/fault.py),
  * an optional chaos port (``chaos_guard=True``): the step takes a third
    traced ``poison`` scalar and multiplies the gradients by NaN whenever
    it is nonzero — an in-graph fault injection that exercises the guard
    without recompiling (train/chaos.py plans WHEN it fires).  With
    ``poison == 0`` the factor is exactly 1.0, so the arithmetic is
    bit-identical to a chaos-free step,
  * an optional data-parallel gradient reduction (``grad_axis``): the
    step pmean-reduces gradients over that named axis (for use inside a
    ``shard_map``), and with ``compress_grads=True`` the reduction runs
    through the int8 error-feedback compressor
    (``optim.compression.psum_compressed_ef``) with the per-member
    residual carried in ``state["opt"]["ef"]`` — the
    ``SPMConfig.compress_pod_grads`` knob.  ``make_pod_train_step`` wraps
    the whole step in that shard_map over a ("pod",) mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.optim.adamw import OptimizerConfig, adamw_update
from repro.optim.compression import psum_compressed_ef

__all__ = ["make_train_step", "make_pod_train_step", "pod_residual",
           "make_eval_step"]


def _split_microbatches(batch: Any, accum_steps: int) -> Any:
    def re(x):
        b = x.shape[0]
        assert b % accum_steps == 0, (b, accum_steps)
        return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])
    return jax.tree.map(re, batch)


def make_train_step(loss_fn: Callable, opt_cfg: OptimizerConfig, *,
                    accum_steps: int = 1,
                    nan_guard: bool = True,
                    chaos_guard: bool = False,
                    grad_axis: Optional[str] = None,
                    compress_grads: bool = False) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics).

    With ``chaos_guard=True`` the returned step is
    ``step(state, batch, poison)`` where ``poison`` is a traced scalar:
    nonzero poisons the gradients with NaN IN-GRAPH (the jitted step stays
    compiled across healthy and poisoned steps), zero multiplies by an
    exact 1.0 — the fault-injection port of train/chaos.py.  Requires
    ``nan_guard`` so the poisoned update is skipped, not applied.

    With ``grad_axis`` the step reduces gradients (and loss/metrics) over
    that named mesh axis — it must then run inside a ``shard_map`` that
    binds the axis.  ``compress_grads=True`` swaps the pmean for the int8
    error-feedback compressed psum; the per-member residual lives in
    ``state["opt"]["ef"]`` (see ``pod_residual``) and rolls back with the
    rest of the optimizer state on NaN-guarded skips.  The chaos poison
    is applied AFTER the reduction so a NaN never enters the int8
    quantizer — the residual update of a poisoned step stays finite and
    is discarded by the same rollback."""
    if chaos_guard and not nan_guard:
        raise ValueError("chaos_guard requires nan_guard (a poisoned "
                         "update must be skipped, not applied)")
    if compress_grads and grad_axis is None:
        raise ValueError("compress_grads requires grad_axis (the int8 "
                         "compressor reduces over a named mesh axis)")

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        mb = _split_microbatches(batch, accum_steps)

        def body(carry, micro):
            acc, loss_acc = carry
            (loss, metrics), grads = grad_fn(params, micro)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, loss_sum), metrics = jax.lax.scan(
            body, (zero, jnp.zeros((), jnp.float32)), mb)
        scale = 1.0 / accum_steps
        grads = jax.tree.map(lambda g: g * scale, gsum)
        # average the stacked per-microbatch metrics — the logged numbers
        # must describe the WHOLE accumulated batch, not the last micro.
        # ce is a masked mean, so a plain mean of per-micro means would
        # skew under uneven masks: weight it by each micro's mask sum
        # (ce_weight from lm_loss) to recover the global masked mean.
        stacked = metrics
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), stacked)
        if (isinstance(stacked, dict) and "ce" in stacked
                and "ce_weight" in stacked):
            w = stacked["ce_weight"]
            wsum = jnp.maximum(jnp.sum(w), 1.0)
            metrics["ce"] = jnp.sum(stacked["ce"] * w) / wsum
            metrics["ce_weight"] = jnp.sum(w)
            if "ppl_proxy" in metrics:
                metrics["ppl_proxy"] = jnp.exp(jnp.clip(metrics["ce"],
                                                        max=20.0))
        return loss_sum * scale, metrics, grads

    def step(state: dict, batch: Any, poison: Any = None):
        loss, metrics, grads = compute_grads(state["params"], batch)
        new_ef = None
        if grad_axis is not None:
            if compress_grads:
                grads, new_ef = psum_compressed_ef(
                    grads, state["opt"]["ef"], grad_axis)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, grad_axis), grads)
            loss = jax.lax.pmean(loss, grad_axis)
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, grad_axis), metrics)
        if chaos_guard:
            if poison is None:
                raise TypeError("chaos_guard step requires the poison "
                                "argument: step(state, batch, poison)")
            # nonzero poison -> NaN factor -> non-finite grad norm -> the
            # nan_guard below skips the update; zero poison multiplies by
            # an EXACT 1.0 so healthy steps are bit-identical to a
            # chaos-free build of the same step.
            factor = jnp.where(jnp.asarray(poison) != 0,
                               jnp.float32(jnp.nan), jnp.float32(1.0))
            grads = jax.tree.map(lambda g: g * factor.astype(g.dtype),
                                 grads)
        new_params, new_opt, info = adamw_update(
            state["params"], grads, state["opt"], opt_cfg)
        if new_ef is not None:
            # adamw passes "ef" through untouched; install the updated
            # residual BEFORE the nan_guard select so a skipped step also
            # rolls the residual back to its pre-step value.
            new_opt = {**new_opt, "ef": new_ef}
        metrics = dict(metrics)
        metrics.update(info)
        if nan_guard:
            with obs.scope("optimizer"):
                ok = jnp.isfinite(info["grad_norm"]) & jnp.isfinite(loss)
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_params,
                    state["params"])
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_opt, state["opt"])
                metrics["skipped"] = (~ok).astype(jnp.float32)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return step


def pod_residual(params: Any, n_pod: int) -> Any:
    """Per-member error-feedback residual for ``make_pod_train_step``.

    Shaped like ``params`` with a leading ``(n_pod,)`` member axis — the
    residual is LOCAL state (each pod member keeps the quantization error
    of its own gradient shard), so it enters the pod step's ``shard_map``
    under ``P(axis)`` while params/optimizer moments stay replicated.
    Store it as ``state["opt"]["ef"]``; AdamW passes unknown optimizer
    keys through untouched and the NaN guard rolls it back with the rest
    of the optimizer state."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_pod,) + p.shape, jnp.float32), params)


def make_pod_train_step(loss_fn: Callable, opt_cfg: OptimizerConfig,
                        mesh, *, axis: str = "pod",
                        compress: bool = True,
                        **step_kwargs) -> Callable:
    """Data-parallel train step over mesh axis ``axis`` via ``shard_map``.

    Wraps ``make_train_step(..., grad_axis=axis,
    compress_grads=compress)`` in a ``shard_map`` over ``mesh``: the
    batch is split along ``axis`` (leading dim), params / optimizer
    moments / step counter are replicated, and — when ``compress`` is on
    (the ``SPMConfig.compress_pod_grads`` knob) — the error-feedback
    residual ``state["opt"]["ef"]`` carries a leading ``(n_pod,)`` member
    axis (see ``pod_residual``) that is sliced to the local member inside
    the body.  Gradients reduce with the int8 error-feedback compressed
    psum (``compress=True``) or a plain pmean; loss and metrics are
    pmean-reduced either way so the returned values are replicated.
    Extra ``step_kwargs`` (``accum_steps``, ``nan_guard``,
    ``chaos_guard``) pass through to ``make_train_step``."""
    step = make_train_step(loss_fn, opt_cfg, grad_axis=axis,
                           compress_grads=compress, **step_kwargs)

    def body(state, batch, poison):
        if compress:
            opt = dict(state["opt"])
            # (1, *shape) local slice of the member-axis residual
            opt["ef"] = jax.tree.map(lambda r: r[0], opt["ef"])
            state = {**state, "opt": opt}
        new_state, metrics = step(state, batch, poison)
        if compress:
            new_opt = dict(new_state["opt"])
            new_opt["ef"] = jax.tree.map(lambda r: r[None], new_opt["ef"])
            new_state = {**new_state, "opt": new_opt}
        return new_state, metrics

    opt_spec = {"mu": P(), "nu": P(), "count": P()}
    if compress:
        opt_spec["ef"] = P(axis)
    state_spec = {"params": P(), "opt": opt_spec, "step": P()}
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(axis), P()),
        out_specs=(state_spec, P()),
        check_vma=False)

    def pod_step(state: dict, batch: Any, poison: Any = None):
        if poison is None:
            poison = jnp.zeros((), jnp.float32)
        return sharded(state, batch, jnp.asarray(poison))

    return pod_step


def make_eval_step(loss_fn: Callable) -> Callable:
    def step(params, batch):
        _, metrics = loss_fn(params, batch)
        return metrics
    return step
