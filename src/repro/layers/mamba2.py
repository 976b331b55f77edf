"""Mamba2 / SSD (state-space duality) mixer, chunked-scan formulation.

Implements the SSD algorithm of arXiv:2405.21060: the sequence is split
into chunks; within a chunk the output is the quadratic "attention-like"
form masked by the cumulative decay matrix L; across chunks an O(T/Q)
``lax.scan`` carries the (H, P, N) recurrent state.  Decode is the O(1)
recurrence ``h <- a h + dt B x``.

TPU adaptation: chunk length defaults to 128 so the intra-chunk einsums
are MXU-shaped (128-aligned); the inter-chunk scan is sequential but tiny.
in/out projections route through the linear factory (SPM-able — the SSD
scan itself is already sub-quadratic and is left untouched, DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.linear import LinearConfig, init_linear, linear_apply
from repro.layers.norms import init_rms_norm, rms_norm

__all__ = ["Mamba2Config", "init_mamba2", "mamba2_apply", "init_ssm_cache"]


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_model: int
    d_state: int = 64
    d_head: int = 64               # P
    expand: int = 2
    d_conv: int = 4
    chunk: int = 128
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
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.d_head

    @property
    def d_in_proj(self) -> int:
        # [z, x, B, C, dt]  (single SSM group)
        return 2 * self.d_inner + 2 * self.d_state + self.n_heads

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
    def in_proj(self) -> LinearConfig:
        return self._lin(self.d_model, self.d_in_proj)

    @property
    def out_proj(self) -> LinearConfig:
        return self._lin(self.d_inner, self.d_model)


def init_mamba2(key: jax.Array, cfg: Mamba2Config) -> dict:
    ki, ko, kc, kd = jax.random.split(key, 4)
    H = cfg.n_heads
    conv_dim = cfg.d_inner + 2 * cfg.d_state
    dt = jnp.exp(jax.random.uniform(kd, (H,), cfg.param_dtype)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {
        "in_proj": init_linear(ki, cfg.in_proj),
        "out_proj": init_linear(ko, cfg.out_proj),
        "conv_w": 0.1 * jax.random.normal(
            kc, (cfg.d_conv, conv_dim), cfg.param_dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.param_dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(cfg.param_dtype)),
        "D": jnp.ones((H,), cfg.param_dtype),
        "dt_bias": jnp.log(jnp.expm1(dt)),   # softplus^-1(dt)
        "norm": init_rms_norm(cfg.d_inner, cfg.param_dtype),
    }


def init_ssm_cache(batch: int, cfg: Mamba2Config, dtype=jnp.float32) -> dict:
    conv_dim = cfg.d_inner + 2 * cfg.d_state
    return {
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.d_head, cfg.d_state), dtype),
        "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_dim), dtype),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: a (..., Q) -> (..., Q, Q) lower-tri cumulative
    sums  out[i, j] = sum_{k=j+1..i} a[k]  (−inf above the diagonal)."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def _ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """SSD scan.  x: (b, T, H, P); dt: (b, T, H); A: (H,);
    B, C: (b, T, N).  Returns y (b, T, H, P), final state (b, H, P, N)."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    while T % Q:
        Q -= 1
    nc = T // Q

    xd = x * dt[..., None]                     # fold dt into inputs
    a = dt * (-jnp.exp(A))                     # log-decay per step (b,T,H)

    xc = xd.reshape(b, nc, Q, H, P)
    ac = a.reshape(b, nc, Q, H)
    Bc = B.reshape(b, nc, Q, N)
    Cc = C.reshape(b, nc, Q, N)

    acs = jnp.cumsum(ac, axis=2)               # (b,nc,Q,H)
    L = jnp.exp(_segsum(jnp.moveaxis(ac, -1, -2)))   # (b,nc,H,Q,Q)

    # intra-chunk (diagonal block): y = (C B^T ⊙ L) x
    cb = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc)       # (b,nc,Q,Q)
    yd = jnp.einsum("bcqs,bchqs,bcshp->bcqhp", cb, L, xc)

    # chunk-final states: h_c = sum_s exp(acs_Q - acs_s) B_s x_s
    decay_to_end = jnp.exp(acs[:, :, -1:, :] - acs)  # (b,nc,Q,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchpn",
                        Bc, decay_to_end, xc)        # (b,nc,H,P,N)

    # inter-chunk recurrence over nc (sequential, tiny)
    chunk_decay = jnp.exp(acs[:, :, -1, :])          # (b,nc,H)

    def body(h, inp):
        st, dec = inp                                # (b,H,P,N), (b,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h                              # emit state BEFORE chunk

    h0 = jnp.zeros((b, H, P, N), x.dtype)
    h_final, h_prev = jax.lax.scan(
        body, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)              # (b,nc,H,P,N)

    # inter-chunk contribution: y += C_t exp(acs_t) h_prev
    in_decay = jnp.exp(acs)                          # (b,nc,Q,H)
    yi = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, in_decay, h_prev)

    y = (yd + yi).reshape(b, T, H, P) + x * D[None, None, :, None]
    return y, h_final


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv.  u: (B, T, C); w: (K, C)."""
    K = w.shape[0]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(K):
        out = out + up[:, i: i + u.shape[1], :] * w[i]
    return out + b


@obs.scoped("ssm")
def mamba2_apply(params: dict, x: jax.Array, cfg: Mamba2Config, *,
                 cache: Optional[dict] = None
                 ) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, T, d).  Returns (y, new_cache).  cache given => T == 1 decode."""
    Bsz, T, _ = x.shape
    H, P, N = cfg.n_heads, cfg.d_head, cfg.d_state
    zxbcdt = linear_apply(params["in_proj"], x, cfg.in_proj)
    z, xin, Bv, Cv, dt = jnp.split(
        zxbcdt, [cfg.d_inner, 2 * cfg.d_inner,
                 2 * cfg.d_inner + N, 2 * cfg.d_inner + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xin, Bv, Cv], axis=-1)
    w, bconv = params["conv_w"].astype(x.dtype), params["conv_b"].astype(x.dtype)

    if cache is None:
        conv = jax.nn.silu(_causal_conv(conv_in, w, bconv))
        new_cache = None
    else:
        hist = jnp.concatenate(
            [cache["conv"].astype(x.dtype), conv_in], axis=1)
        acc = bconv + jnp.einsum("kc,bkc->bc", w, hist)[:, None, :]
        conv = jax.nn.silu(acc)
        new_conv = hist[:, 1:, :]
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype)}

    xc, Bc, Cc = jnp.split(conv, [cfg.d_inner, cfg.d_inner + N], axis=-1)
    xh = xc.reshape(Bsz, T, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = params["A_log"].astype(jnp.float32)
    D = params["D"].astype(jnp.float32)

    if cache is None:
        y, _ = _ssd_chunked(xh.astype(jnp.float32), dt, A,
                            Bc.astype(jnp.float32), Cc.astype(jnp.float32),
                            D, cfg.chunk)
    else:
        # O(1) recurrent step:  h <- exp(-exp(A) dt) h + dt B x
        a = jnp.exp(dt[:, 0, :] * (-jnp.exp(A)))          # (B,H)
        h = cache["ssm"].astype(jnp.float32)
        upd = jnp.einsum("bh,bhp,bn->bhpn",
                         dt[:, 0, :], xh[:, 0].astype(jnp.float32),
                         Bc[:, 0].astype(jnp.float32))
        h = h * a[..., None, None] + upd
        yv = jnp.einsum("bhpn,bn->bhp", h, Cc[:, 0].astype(jnp.float32))
        y = (yv + xh[:, 0].astype(jnp.float32)
             * D[None, :, None])[:, None]
        new_cache["ssm"] = h.astype(cache["ssm"].dtype)

    y = y.reshape(Bsz, T, cfg.d_inner).astype(x.dtype)
    y = rms_norm(params["norm"], y * jax.nn.silu(z))
    return linear_apply(params["out_proj"], y, cfg.out_proj), new_cache
