"""Mixture-of-Experts FFN with GShard-style capacity dispatch.

The dispatch/combine einsums are the expert-parallel (EP) communication
pattern: with experts sharded over the ``model`` mesh axis and tokens over
``data``, XLA's SPMD partitioner lowers them to all-to-alls.  The router
stays a dense ``d -> E`` map — it is a tiny classifier head, not a square
feature mixer, so SPM is inapplicable by design (DESIGN.md §4).

Per-expert FFN weights DO route through the linear factory, so SPM applies
inside each expert (``vmap`` over the expert axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.layers.ffn import FFNConfig, init_ffn, ffn_apply

__all__ = ["MoEConfig", "init_moe", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert hidden dim
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 512     # GShard "S": dispatch is computed per token
                              # group, so the one-hot tensor is
                              # (G, S, E, C) with C ~ k*S/E — total memory
                              # O(N * k * S), NOT O(N * E * C_global).
    shared_d_ff: int = 0      # Llama4-style always-on shared expert (0 = off)
    linear_impl: str = "dense"
    spm_stages: Optional[int] = None
    spm_backward: str = "autodiff"
    spm_use_kernel: Optional[bool] = None
    spm_schedule: str = "butterfly"
    spm_n_shards: int = 1
    spm_overlap: Optional[bool] = None
    spm_quant_acts: bool = False
    spm_quant_coeffs: bool = False
    param_dtype: Any = jnp.float32

    @property
    def expert_ffn(self) -> FFNConfig:
        return FFNConfig(d_model=self.d_model, d_ff=self.d_ff,
                         linear_impl=self.linear_impl,
                         spm_stages=self.spm_stages,
                         spm_backward=self.spm_backward,
                         spm_use_kernel=self.spm_use_kernel,
                         spm_schedule=self.spm_schedule,
                         spm_n_shards=self.spm_n_shards,
                         spm_overlap=self.spm_overlap,
                         spm_quant_acts=self.spm_quant_acts,
                         spm_quant_coeffs=self.spm_quant_coeffs,
                         param_dtype=self.param_dtype)

    @property
    def shared_ffn(self) -> FFNConfig:
        return FFNConfig(d_model=self.d_model, d_ff=self.shared_d_ff,
                         linear_impl=self.linear_impl,
                         spm_stages=self.spm_stages,
                         spm_backward=self.spm_backward,
                         spm_use_kernel=self.spm_use_kernel,
                         spm_schedule=self.spm_schedule,
                         spm_n_shards=self.spm_n_shards,
                         spm_overlap=self.spm_overlap,
                         spm_quant_acts=self.spm_quant_acts,
                         spm_quant_coeffs=self.spm_quant_coeffs,
                         param_dtype=self.param_dtype)

    def capacity(self, group_tokens: int) -> int:
        c = int(self.capacity_factor * self.top_k * group_tokens
                / self.n_experts)
        return max(c, self.top_k)


def init_moe(key: jax.Array, cfg: MoEConfig) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    p = {
        "router": 0.02 * jax.random.normal(
            kr, (cfg.d_model, cfg.n_experts), cfg.param_dtype),
        "experts": jax.vmap(lambda k: init_ffn(k, cfg.expert_ffn))(
            jax.random.split(ke, cfg.n_experts)),
    }
    if cfg.shared_d_ff:
        p["shared"] = init_ffn(ks, cfg.shared_ffn)
    return p


def _top_k_gating(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """logits (..., E) -> (gates (..., E) renormalized over chosen, mask)."""
    topv, topi = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(topv, axis=-1)                 # renorm over top-k
    onehot = jax.nn.one_hot(topi, logits.shape[-1],
                            dtype=logits.dtype)           # (..., k, E)
    gates = jnp.einsum("...k,...ke->...e", probs, onehot)
    mask = jnp.sum(onehot, axis=-2) > 0
    return gates, mask


@obs.scoped("moe")
def moe_apply(params: dict, x: jax.Array, cfg: MoEConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, d) -> (y, aux_loss).  aux is the load-balancing loss
    (Switch-style mean(gate_frac * token_frac) * E).

    GShard grouped dispatch: tokens are split into G groups of S; routing
    capacity is per (group, expert), so the dispatch one-hot is
    (G, S, E, C) with C = ceil(cf * k * S / E).  With G sharded over
    ``data`` and experts over ``model``, the two einsums below lower to
    the canonical EP all-to-all pair.
    """
    B, T, d = x.shape
    n_tok = B * T
    S = min(cfg.group_size, n_tok)
    while n_tok % S:
        S -= 1
    G = n_tok // S
    cap = cfg.capacity(S)

    xg = x.reshape(G, S, d)
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gates, mask = _top_k_gating(logits, cfg.top_k)        # (G, S, E)

    # load-balancing aux loss (global means)
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=(0, 1))
    ce = jnp.mean(mask.astype(jnp.float32),
                  axis=(0, 1)) * cfg.n_experts / cfg.top_k
    aux = jnp.sum(me * ce)

    # capacity-limited positions: rank within (group, expert)
    maskf = mask.astype(jnp.int32)
    pos = jnp.cumsum(maskf, axis=1) - 1                   # (G, S, E)
    keep = mask & (pos < cap)
    gates = jnp.where(keep, gates, 0.0)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, -1), cap,
                            dtype=x.dtype)                # (G, S, E, C)

    dispatch = pos_oh
    combine = gates.astype(x.dtype)[..., None] * pos_oh

    xe = jnp.einsum("gsec,gsd->egcd", dispatch, xg)       # EP all-to-all
    E = cfg.n_experts
    ye = jax.vmap(lambda p, h: ffn_apply(p, h, cfg.expert_ffn)
                  )(params["experts"], xe.reshape(E, G * cap, d))
    ye = ye.reshape(E, G, cap, d)
    yg = jnp.einsum("gsec,egcd->gsd", combine, ye)        # EP all-to-all

    y = yg.reshape(B, T, d)
    if cfg.shared_d_ff:
        y = y + ffn_apply(params["shared"], x, cfg.shared_ffn)
    return y.astype(x.dtype), aux
