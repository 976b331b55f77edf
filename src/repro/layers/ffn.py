"""Feed-forward block (projections via the linear factory).

Two shapes, selected by ``FFNConfig.activation``:

  * ``"swiglu"`` (default) — gated: ``down(silu(gate(x)) * up(x))``.
  * ``"relu" | "silu" | "gelu"`` — ungated: ``down(act(up(x)))``, no gate
    parameters.  These are the shapes the residual-block megakernel can
    lower as ONE fused Pallas region (``ffn_block_apply``): the gate of
    swiglu is a second independent SPM over the same input, not a
    chainable elementwise epilogue, so swiglu always takes the per-linear
    path.

``ffn_block_apply`` is the fused residual-block entry used by the
transformer: ``x + ffn(rms_norm(x))`` with norm prologue, activation
epilogue, and residual store inside the kernel chain when
``core/eligibility.resolve_block_fuse`` engages, and the bitwise XLA /
per-linear-kernel composition otherwise.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.eligibility import block_fusion_eligible, resolve_block_fuse
from repro.core.linear import (LinearConfig, init_linear, linear_apply,
                               spm_block_operands)
from repro.layers.norms import rms_norm

__all__ = ["FFNConfig", "init_ffn", "ffn_apply", "ffn_block_apply"]

_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}


@dataclasses.dataclass(frozen=True)
class FFNConfig:
    d_model: int
    d_ff: int
    linear_impl: str = "dense"
    activation: str = "swiglu"           # "swiglu" | "relu" | "silu" | "gelu"
    spm_stages: Optional[int] = None
    spm_backward: str = "autodiff"
    spm_use_kernel: Optional[bool] = None
    spm_schedule: str = "butterfly"
    spm_n_shards: int = 1
    spm_overlap: Optional[bool] = None
    spm_quant_acts: bool = False
    spm_quant_coeffs: bool = False
    # Residual-block megakernel (norm -> up -> act -> down -> residual in
    # one Pallas chain): tri-state like spm_use_kernel.  None = auto
    # (on-TPU), True = force (interpret off-TPU), False = per-linear path.
    # Only engages for ungated activations on block-fusible SPM linears
    # (core/eligibility.block_fusion_eligible); falls back gracefully.
    spm_block_fuse: Optional[bool] = None
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.activation != "swiglu" and self.activation not in _ACTS:
            raise ValueError(f"unknown ffn activation {self.activation!r}")

    def _lin(self, d_in: int, d_out: int) -> LinearConfig:
        return LinearConfig(
            d_in=d_in, d_out=d_out, impl=self.linear_impl, use_bias=False,
            n_stages=self.spm_stages, backward=self.spm_backward,
            use_kernel=self.spm_use_kernel, schedule=self.spm_schedule,
            n_shards=self.spm_n_shards, overlap=self.spm_overlap,
            quant_acts=self.spm_quant_acts,
            quant_coeffs=self.spm_quant_coeffs,
            param_dtype=self.param_dtype)

    @property
    def up(self) -> LinearConfig:
        return self._lin(self.d_model, self.d_ff)

    @property
    def gate(self) -> LinearConfig:
        return self._lin(self.d_model, self.d_ff)

    @property
    def down(self) -> LinearConfig:
        return self._lin(self.d_ff, self.d_model)


def init_ffn(key: jax.Array, cfg: FFNConfig) -> dict:
    """Init the block's linears (no gate for ungated activations)."""
    ku, kg, kd = jax.random.split(key, 3)
    p = {"up": init_linear(ku, cfg.up), "down": init_linear(kd, cfg.down)}
    if cfg.activation == "swiglu":
        p["gate"] = init_linear(kg, cfg.gate)
    return p


def ffn_apply(params: dict, x: jax.Array, cfg: FFNConfig) -> jax.Array:
    """The FFN body alone (no norm, no residual): gated swiglu or
    ``down(act(up(x)))`` per ``cfg.activation``."""
    u = linear_apply(params["up"], x, cfg.up)
    if cfg.activation == "swiglu":
        g = linear_apply(params["gate"], x, cfg.gate)
        h = jax.nn.silu(g) * u
    else:
        h = _ACTS[cfg.activation](u)
    return linear_apply(params["down"], h, cfg.down)


def _block_bundles(params: dict, cfg: FFNConfig):
    """The (up, down) kernel-operand bundles when this FFN is structurally
    block-fusible, else None: ungated activation, both linears
    block-fusible SPM stacks sharing one operator width."""
    if cfg.activation == "swiglu":
        return None
    up = spm_block_operands(params["up"], cfg.up)
    if up is None:
        return None
    down = spm_block_operands(params["down"], cfg.down)
    if down is None or down["n"] != up["n"]:
        return None
    if not block_fusion_eligible(up["n"], up["strides"], down["strides"],
                                 cfg.activation):
        return None
    return up, down


@obs.scoped("ffn")
def ffn_block_apply(params: dict, norm_params: Optional[dict], x: jax.Array,
                    cfg: FFNConfig) -> jax.Array:
    """The whole residual block: ``x + ffn(rms_norm(x))``.

    When ``resolve_block_fuse`` engages (tri-state ``cfg.spm_block_fuse``
    over structural eligibility), the block lowers as ONE fused Pallas
    region — RMS prologue, up-stack, activation epilogue, down-stack, and
    residual-add on the store, with the closed-form block custom_vjp
    (``kernels/ops.spm_block_fused``).  Otherwise the composition below is
    literally the pre-existing per-linear path (bitwise fallback).
    ``norm_params=None`` skips the norm (block without prologue)."""
    bundles = _block_bundles(params, cfg)
    fuse = resolve_block_fuse(cfg.spm_block_fuse, bundles is not None,
                              jax.default_backend() == "tpu")
    if fuse:
        from repro.kernels import ops as kernel_ops  # lazy: keeps layers light
        up, down = bundles
        gamma = (norm_params["scale"] if norm_params is not None else None)
        return kernel_ops.spm_block_fused(
            x, coeffs1=up["coeffs"], d_in1=up["d_in"], d_out1=up["d_out"],
            bias1=up["bias"], strides1=up["strides"], gamma=gamma,
            coeffs2=down["coeffs"], d_in2=down["d_in"],
            d_out2=down["d_out"], bias2=down["bias"],
            strides2=down["strides"], activation=cfg.activation,
            residual=True, mid_width=cfg.d_ff, out_width=cfg.d_model)
    h = rms_norm(norm_params, x) if norm_params is not None else x
    return x + ffn_apply(params, h, cfg)
