"""Mixture-of-Experts FFN with dropless routing over row tiles.

The router is a dense ``d -> E`` map in f32 — a tiny classifier head, not
a square feature mixer, so SPM is inapplicable by design (DESIGN.md §4).
Each token picks its ``top_k`` experts; the gates are the softmax over
all experts renormalised over the chosen ones.

Dispatch never drops a token.  The ``N * k`` (token, expert) assignments
are sorted by expert, and each expert's group is padded to a multiple of
a row tile ``t`` (``ROW_TILE``, less for small batches) inside one
static buffer of ``N * k + E * (t - 1)`` rows (rounded up to whole
tiles) — the most the padded groups can take.  Every tile belongs to one
expert (``tile_expert``); tiles past the last group hold zeros and are
never combined.  The expert SwiGLU runs through the ordinary
``ffn_apply`` / ``linear_apply`` path, ``vmap``-ped over tiles with each
tile's parameters gathered from its expert, so SPM experts run the fused
SPM kernel (custom_vjp) and autodiff of the gather sums each tile's
parameter gradient into its expert.  The outputs are gathered back per
assignment and combined with the gates in f32.

The layer also returns the per-expert routing statistics that the
load-balancing loss needs and counters of the dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.layers.ffn import FFNConfig, init_ffn, ffn_apply

__all__ = ["MoEConfig", "init_moe", "moe_apply", "dispatch_plan",
           "load_balancing_loss"]

MIN_TILE = 8      # row tile floor: one sublane group of the SPM kernel
ROW_TILE = 128    # row tile at training sizes (a power of two)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert hidden dim
    n_experts: int
    top_k: int
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

    def _ffn(self, d_ff: int) -> FFNConfig:
        return FFNConfig(d_model=self.d_model, d_ff=d_ff,
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
    def expert_ffn(self) -> FFNConfig:
        return self._ffn(self.d_ff)

    @property
    def shared_ffn(self) -> FFNConfig:
        return self._ffn(self.shared_d_ff)

    def tile_rows(self, n_tokens: int) -> int:
        """The row tile for ``n_tokens`` tokens: ``ROW_TILE``, or less
        when the mean expert load is smaller (decode), so the buffer's
        padding ``E * (t - 1)`` stays near the routed rows."""
        mean = -(-n_tokens * self.top_k // self.n_experts)
        t = MIN_TILE
        while t < min(mean, ROW_TILE):
            t *= 2
        return t


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


def dispatch_plan(experts: jax.Array, n_experts: int, tile: int) -> dict:
    """Where each assignment goes in the tile buffer.

    ``experts``: (A,) expert id of each assignment.  Returns ``dest`` (A,)
    its buffer row, ``src`` (R,) the assignment each buffer row holds (A
    for padding), ``tile_expert`` (R / tile,) the expert of each tile and
    ``counts`` (E,) the rows routed to each expert, with
    ``R = ceil((A + E * (tile - 1)) / tile) * tile``."""
    A = experts.shape[0]
    n_tiles = -(-(A + n_experts * (tile - 1)) // tile)
    counts = jnp.bincount(experts, length=n_experts)
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)                       # (E,) in tiles
    start = (tile_end - tiles) * tile                  # first row per expert
    first = jnp.cumsum(counts) - counts                # sorted offset
    order = jnp.argsort(experts, stable=True)
    e_sorted = experts[order]
    dest_sorted = start[e_sorted] + jnp.arange(A) - first[e_sorted]
    dest = jnp.zeros((A,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32), unique_indices=True)
    src = jnp.full((n_tiles * tile,), A, jnp.int32).at[dest].set(
        jnp.arange(A, dtype=jnp.int32), unique_indices=True)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_experts - 1).astype(jnp.int32)
    return {"dest": dest, "src": src, "tile_expert": tile_expert,
            "counts": counts}


def load_balancing_loss(frac: jax.Array, prob: jax.Array) -> jax.Array:
    """HF ``load_balancing_loss_func``: ``E * sum_e frac_e * prob_e`` with
    ``frac_e`` the share of tokens that chose expert ``e`` (summing to
    ``top_k``) and ``prob_e`` its mean router probability, both averaged
    over every routed token of every layer."""
    return frac.shape[-1] * jnp.sum(frac * prob)


@obs.scoped("moe")
def moe_apply(params: dict, x: jax.Array, cfg: MoEConfig
              ) -> Tuple[jax.Array, dict]:
    """x: (B, T, d) -> (y, stats).

    ``stats``: ``frac`` and ``prob`` (E,), this layer's share of tokens
    per expert and mean router probability (``load_balancing_loss``);
    ``switch``, this layer's load-balancing loss over ``top_k``;
    ``rows``, the routed assignments; ``pad_rows`` and ``buffer_rows``,
    the rows padding the experts' groups to whole tiles and the buffer's
    rows; ``load_max``, the largest expert's rows over the mean."""
    B, T, d = x.shape
    N, E, k = B * T, cfg.n_experts, cfg.top_k
    xt = x.reshape(N, d)
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                  # (N, k)
    gates = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalised

    tile = cfg.tile_rows(N)
    plan = dispatch_plan(topi.reshape(-1), E, tile)
    tok = jnp.concatenate([jnp.arange(N * k) // k,
                           jnp.array([N])])[plan["src"]]
    xbuf = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])[tok]
    n_tiles = plan["tile_expert"].shape[0]
    tile_params = jax.tree.map(
        lambda a: a.at[plan["tile_expert"]].get(indices_are_sorted=True),
        params["experts"])
    ybuf = jax.vmap(lambda p, h: ffn_apply(p, h, cfg.expert_ffn))(
        tile_params, xbuf.reshape(n_tiles, tile, d)).reshape(-1, d)
    ya = ybuf[plan["dest"]].reshape(N, k, d).astype(jnp.float32)
    y = jnp.sum(gates[..., None] * ya, axis=1).reshape(B, T, d)
    if cfg.shared_d_ff:
        y = y + ffn_apply(params["shared"], x, cfg.shared_ffn)

    counts = plan["counts"].astype(jnp.float32)
    padded = jnp.sum(-(-plan["counts"] // tile) * tile).astype(jnp.float32)
    frac, prob = counts / N, jnp.mean(probs, axis=0)
    stats = {"frac": frac, "prob": prob,
             "switch": load_balancing_loss(frac, prob) / k,
             "rows": jnp.float32(N * k), "pad_rows": padded - N * k,
             "buffer_rows": jnp.float32(n_tiles * tile),
             "load_max": jnp.max(counts) * E / (N * k)}
    return y.astype(x.dtype), stats
