"""GQA attention: chunked (flash-style) training path + KV-cache decode.

Projections go through the ``linear_impl`` factory so the paper's SPM
operator can replace every dense Q/K/V/O map (paper §7).  The score
computation ``Q K^T`` is untouched (paper §7.2: "attention score
computation remains unchanged").

The training/prefill path is an online-softmax over key chunks written
with ``jax.lax`` control flow: memory is O(T * chunk) instead of O(T^2),
which is what lets the 32k-prefill dry-run cells fit HBM.  Sliding-window
(Gemma3 local layers) is a mask refinement of the same loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.eligibility import resolve_block_fuse
from repro.core.linear import (LinearConfig, init_linear, linear_apply,
                               spm_block_operands)
from repro.layers.norms import qk_norm, rms_norm
from repro.layers.rope import apply_rope
from repro.parallel.ctx import constrain

__all__ = ["AttentionConfig", "init_attention", "attention_apply",
           "init_kv_cache", "chunked_causal_attention"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    use_qk_norm: bool = False
    window: Optional[int] = None        # sliding window (None = global)
    linear_impl: str = "dense"
    spm_stages: Optional[int] = None
    spm_backward: str = "autodiff"
    spm_use_kernel: Optional[bool] = None
    spm_schedule: str = "butterfly"
    spm_n_shards: int = 1
    spm_overlap: Optional[bool] = None
    spm_quant_acts: bool = False
    spm_quant_coeffs: bool = False
    # Fused-qkv norm prologue: when ``attention_apply`` receives
    # ``norm_params`` and ALL THREE q/k/v projections are block-fusible
    # SPM stacks, each projection lowers as one norm -> SPM Pallas region
    # (kernels/ops.spm_block_fused, no second stack).  Tri-state like
    # spm_use_kernel; ineligible layers fall back to one explicit
    # rms_norm + the per-linear path (bitwise).
    spm_block_fuse: Optional[bool] = None
    q_chunk: int = 1024
    k_chunk: int = 1024
    param_dtype: Any = jnp.float32

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
    def q_proj(self) -> LinearConfig:
        return self._lin(self.d_model, self.n_heads * self.head_dim)

    @property
    def kv_proj(self) -> LinearConfig:
        return self._lin(self.d_model, self.n_kv_heads * self.head_dim)

    @property
    def o_proj(self) -> LinearConfig:
        return self._lin(self.n_heads * self.head_dim, self.d_model)


def init_attention(key: jax.Array, cfg: AttentionConfig) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "q": init_linear(kq, cfg.q_proj),
        "k": init_linear(kk, cfg.kv_proj),
        "v": init_linear(kv, cfg.kv_proj),
        "o": init_linear(ko, cfg.o_proj),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.param_dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.param_dtype)
    return p


def init_kv_cache(batch: int, max_len: int, cfg: AttentionConfig,
                  dtype=jnp.bfloat16) -> dict:
    """Decode-time cache.  ``window`` layers allocate only the window."""
    s = max_len if cfg.window is None else min(max_len, cfg.window)
    shape = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# chunked online-softmax attention
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B, Tq, Hkv, G, dh); k: (B, Tk, Hkv, dh) -> (B, Hkv, G, Tq, Tk).

    Standard GQA convention: q head h shares kv head h // G (consecutive
    q heads share one kv head)."""
    return jnp.einsum("bthgd,bshd->bhgts", q, k)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             window: Optional[int] = None,
                             q_offset: int = 0,
                             q_chunk: int = 1024,
                             k_chunk: int = 1024) -> jax.Array:
    """Causal GQA attention with online softmax over key chunks.

    q: (B, Tq, H, dh); k, v: (B, Tk, Hkv, dh) with H % Hkv == 0.
    q position i attends to k positions j <= i + q_offset (and
    j > i + q_offset - window when windowed).  Returns (B, Tq, H, dh).
    """
    B, Tq, H, dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = dh ** -0.5

    # Pad the EDGE chunk (masked) instead of shrinking the chunk to a
    # divisor: the old largest-divisor search degraded to chunk=1 on
    # prime/odd lengths (a T=1021 prefill became a length-1021 scan of
    # single-row chunks).  Padded key positions land past every real
    # position, so the causal mask would admit them for padded queries —
    # the explicit ``kp < Tk`` refinement keeps them out everywhere; padded
    # query rows are sliced off the output.
    q_chunk = min(q_chunk, Tq)
    k_chunk = min(k_chunk, Tk)
    Tq_pad = -(-Tq // q_chunk) * q_chunk
    Tk_pad = -(-Tk // k_chunk) * k_chunk
    if Tq_pad != Tq:
        q = jnp.pad(q, ((0, 0), (0, Tq_pad - Tq), (0, 0), (0, 0)))
    if Tk_pad != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tk_pad - Tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tk_pad - Tk), (0, 0), (0, 0)))
    nq, nk = Tq_pad // q_chunk, Tk_pad // k_chunk

    qg = (q.reshape(B, nq, q_chunk, Hkv, G, dh).astype(jnp.float32) * scale)
    kg = k.reshape(B, nk, k_chunk, Hkv, dh).astype(jnp.float32)
    vg = v.reshape(B, nk, k_chunk, Hkv, dh).astype(jnp.float32)

    q_pos = q_offset + jnp.arange(Tq_pad).reshape(nq, q_chunk)
    k_pos = jnp.arange(Tk_pad).reshape(nk, k_chunk)

    def per_q_chunk(qi, qc):
        # qc: (B, q_chunk, Hkv, G, dh)
        qp = q_pos[qi]  # (q_chunk,)

        def body(carry, inputs):
            m, l, acc = carry
            kc, vc, kp = inputs   # (B,k_chunk,Hkv,dh) x2, (k_chunk,)
            s = _gqa_scores(qc, kc)                       # (B,Hkv,G,qc,kc)
            mask = kp[None, :] <= qp[:, None]             # causal
            if window is not None:
                mask &= kp[None, :] > qp[:, None] - window
            if Tk_pad != Tk:
                mask &= kp[None, :] < Tk                  # padded keys out
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgts,bshd->bhgtd", p, vc)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,Hkv,G,qc,dh)
        return jnp.transpose(out, (0, 3, 1, 2, 4))        # (B,qc,Hkv,G,dh)

    outs = jax.lax.map(lambda i: per_q_chunk(i, qg[:, i]), jnp.arange(nq))
    # outs: (nq, B, q_chunk, G, Hkv, dh)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq_pad, H, dh)
    if Tq_pad != Tq:
        out = out[:, :Tq]
    return out


# ---------------------------------------------------------------------------
# full layer apply
# ---------------------------------------------------------------------------

@obs.scoped("attn")
def attention_apply(params: dict, x: jax.Array, cfg: AttentionConfig, *,
                    cos: jax.Array, sin: jax.Array,
                    cache: Optional[dict] = None,
                    cache_index: Optional[jax.Array] = None,
                    fill_len: Optional[jax.Array] = None,
                    norm_params: Optional[dict] = None
                    ) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, T, d).  ``norm_params`` (the pre-attention RMSNorm scale)
    moves the input norm INSIDE this layer: when ``cfg.spm_block_fuse``
    resolves on and all three q/k/v projections are block-fusible SPM
    stacks, each projection runs as one fused norm -> SPM Pallas region
    (the norm never round-trips HBM); otherwise one explicit ``rms_norm``
    is applied up front — bitwise the caller-side composition.  Three
    modes:

    * **training** — ``cache is None``: chunked causal attention, no cache.
    * **prefill-into-cache** — cache given with ``T > 1``: the fresh
      prompt runs the SAME chunked attention path and its K/V are
      block-written into the (assumed empty) cache in one pass — no
      per-token scan.  ``cache_index`` is the scalar start position
      (serving prefills at 0); ``fill_len`` (scalar or per-row ``(B,)``)
      gives the TRUE prompt length of a right-padded batch: windowed
      layers ring-fill only the last ``window`` REAL positions (padded
      keys never evict real ones), and full layers rely on the decode
      valid mask to hide padded slots until decode overwrites them.
    * **decode** — cache given with ``T == 1``: append K/V at
      ``cache_index`` and attend over the cache.  ``cache_index`` may be
      a scalar (whole batch at one position — the fixed-batch engine) or
      per-row ``(B,)`` (continuous batching: every slot at its own
      length, scatter-written).  Windowed layers treat the cache as a
      ring buffer (slot = index % window, age-based valid mask).
    """
    B, T, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    bundles = None
    if norm_params is not None:
        bq = spm_block_operands(params["q"], cfg.q_proj)
        bk = spm_block_operands(params["k"], cfg.kv_proj)
        bv = spm_block_operands(params["v"], cfg.kv_proj)
        if bq is not None and bk is not None and bv is not None:
            bundles = (bq, bk, bv)
    fuse = (norm_params is not None
            and resolve_block_fuse(cfg.spm_block_fuse, bundles is not None,
                                   jax.default_backend() == "tpu"))
    if fuse:
        from repro.kernels import ops as kernel_ops  # lazy: keeps layers light
        gamma = norm_params["scale"]

        def _norm_proj(b, lcfg):
            return kernel_ops.spm_block_fused(
                x, coeffs1=b["coeffs"], d_in1=b["d_in"], d_out1=b["d_out"],
                bias1=b["bias"], strides1=b["strides"], gamma=gamma,
                out_width=lcfg.d_out)

        q = constrain(_norm_proj(bq, cfg.q_proj)
                      .reshape(B, T, H, dh), "heads")
        k = constrain(_norm_proj(bk, cfg.kv_proj)
                      .reshape(B, T, Hkv, dh), "kv_heads")
        v = constrain(_norm_proj(bv, cfg.kv_proj)
                      .reshape(B, T, Hkv, dh), "kv_heads")
    else:
        if norm_params is not None:
            x = rms_norm(norm_params, x)
        q = constrain(linear_apply(params["q"], x, cfg.q_proj)
                      .reshape(B, T, H, dh), "heads")
        k = constrain(linear_apply(params["k"], x, cfg.kv_proj)
                      .reshape(B, T, Hkv, dh), "kv_heads")
        v = constrain(linear_apply(params["v"], x, cfg.kv_proj)
                      .reshape(B, T, Hkv, dh), "kv_heads")

    if cfg.use_qk_norm:
        q = qk_norm(params["q_norm"], q)
        k = qk_norm(params["k_norm"], k)

    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is None:
        out = chunked_causal_attention(
            q, k, v, window=cfg.window,
            q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)
        new_cache = None
    elif T > 1:
        # prefill-into-cache: attention over the fresh prompt runs the
        # chunked training path (cache assumed empty), then K/V are
        # block-written in one pass.
        out = chunked_causal_attention(
            q, k, v, window=cfg.window,
            q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)
        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        s_cache = cache["k"].shape[1]
        start = jnp.asarray(0 if cache_index is None else cache_index)
        if cfg.window is None:
            ck = jax.lax.dynamic_update_slice(cache["k"], kc, (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], vc, (0, start, 0, 0))
        else:
            # ring fill: slot j holds the newest position p with
            # p % window == j among the REAL positions start..last; with a
            # right-padded prompt, ``fill_len`` keeps padded keys out of
            # the ring so they can never evict real recent positions.
            lens = jnp.broadcast_to(
                jnp.asarray(T if fill_len is None else fill_len), (B,))
            last = start + lens - 1                          # (B,) global
            j = jnp.arange(s_cache)[None, :]                 # (1, W)
            p = last[:, None] - ((last[:, None] - j) % s_cache)
            src = jnp.clip(p - start, 0, T - 1)              # (B, W)
            ck = jnp.take_along_axis(kc, src[:, :, None, None], axis=1)
            cv = jnp.take_along_axis(vc, src[:, :, None, None], axis=1)
        new_cache = {"k": ck, "v": cv}
    else:
        # decode: append k/v at cache_index (ring-buffer for windowed
        # layers); per-row (B,) cache_index scatter-writes each row at its
        # own slot — the continuous-batching path.
        ci = jnp.asarray(cache_index)
        s_cache = cache["k"].shape[1]
        slot = (ci % s_cache) if cfg.window is not None else ci
        if ci.ndim == 1:
            rows = jnp.arange(B)
            ck = cache["k"].at[rows, slot].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slot].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": ck, "v": cv}
        scale = dh ** -0.5
        qf = q.astype(jnp.float32) * scale                 # (B,1,H,dh)
        kf = ck.astype(jnp.float32)
        vf = cv.astype(jnp.float32)
        qg = qf.reshape(B, 1, Hkv, H // Hkv, dh)
        s = jnp.einsum("bthgd,bshd->bhgts", qg, kf)        # (B,Hkv,G,1,S)
        pos = jnp.arange(s_cache)[None, :]                 # (1, S)
        ci_b = jnp.broadcast_to(ci, (B,))[:, None]         # (B, 1)
        if cfg.window is None:
            valid = pos <= ci_b                            # (B, S)
        else:
            # ring buffer: valid slots are the last min(index+1, window)
            n_valid = jnp.minimum(ci_b + 1, s_cache)
            slot_b = jnp.broadcast_to(slot, (B,))[:, None]
            age = (slot_b - pos) % s_cache                 # 0 = newest
            valid = age < n_valid
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgts,bshd->bthgd", p, vf).reshape(B, 1, H, dh)

    out = out.astype(x.dtype).reshape(B, T, H * dh)
    y = linear_apply(params["o"], out, cfg.o_proj)
    return y, new_cache
