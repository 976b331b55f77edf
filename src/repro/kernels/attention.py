"""Causal self-attention over a fresh sequence as a Pallas flash kernel.

``causal_attention(q, k, v)`` takes q (B, T, H, dh) and k, v
(B, T, Hkv, dh) and returns (B, T, H, dh): the contract of
``layers.attention.chunked_causal_attention`` with ``q_offset=0`` and no
window.  It runs JAX's TPU splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``) as its MQA
variant, one kernel per (batch row, KV head) through ``vmap``, over the
G = H // Hkv query heads that share that KV head (q head h reads KV head
h // G, the grouping of ``layers.attention._gqa_scores``):

* block-sparse: query/key blocks wholly above the causal diagonal are
  never visited, forward or backward;
* a forward and one fused backward kernel (dq, dk and dv together)
  under one ``custom_vjp``; the forward saves only its output and the
  per-row log-sum-exp;
* precision: q, k and v enter in the activation dtype (bf16 in the
  model), scores and the running max and sum are f32, P.V accumulates in
  f32.  The 1/sqrt(dh) scale is applied to q in f32 and rounded once to
  the activation dtype.

Block sizes are a function of the sequence length only (``block_sizes``).
The mask processing is numpy on the host, so the kernel object is built
once per (T, G, blocks, interpret) and cached.  Off-TPU the kernels run
in interpret mode (``kernels.ops.default_interpret``).  The kernels' op
names start ``splash_mqa_`` (never ``spm``, which names the SPM kernels).

``layers.attention`` decides when this path runs (``supported`` is its
shape test); docs/kernels.md "Attention kernel" has the rules.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash,
    splash_attention_mask as mask_lib,
)

from repro.kernels import ops

__all__ = ["causal_attention", "supported", "block_sizes"]

LANES = 128
# Preferred block edge for queries and keys, shrunk to a divisor of T.  A
# v5e sweep (PERF.md, "Findings") put 1024 x 1024 blocks with the fused dq/dkv
# backward first at T 4096 (16/8 heads of 128), and blocks of min(T, 512
# or 1024) at or near the best for every prefill bucket 128-1024 (64/8
# heads); 128-wide blocks lost up to 3.5x at T 1024.
BLOCK = 1024


def supported(seq_len: int, head_dim: int) -> bool:
    """Whether the kernel takes a sequence of ``seq_len`` with heads of
    ``head_dim``: both whole multiples of the 128-lane tile."""
    return seq_len % LANES == 0 and head_dim % LANES == 0


def _fit(pref: int, seq_len: int) -> int:
    """The largest multiple of 128 that is at most ``pref`` and divides
    ``seq_len`` (itself a multiple of 128)."""
    b = min(pref, seq_len)
    while seq_len % b:
        b -= LANES
    return b


def block_sizes(seq_len: int) -> splash.BlockSizes:
    """The kernel's block sizes at sequence length ``seq_len``: square
    blocks, the same in the forward and in the fused backward (dq and dkv
    in one kernel)."""
    b = _fit(BLOCK, seq_len)
    return splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def _mqa_kernel(seq_len: int, group: int, blocks: splash.BlockSizes,
                interpret: bool):
    mask = mask_lib.MultiHeadMask(
        [mask_lib.CausalMask((seq_len, seq_len))] * group)
    # the kernel holds its block maps as device arrays: make them concrete
    # even when the first call comes from inside a trace
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=blocks, interpret=interpret)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal GQA attention of a fresh sequence (position i sees keys
    0..i).  q: (B, T, H, dh); k, v: (B, T, Hkv, dh).  Returns
    (B, T, H, dh) in q's dtype."""
    B, T, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if not supported(T, dh):
        raise ValueError(f"no kernel for T={T}, head_dim={dh}")
    kern = _mqa_kernel(T, G, block_sizes(T),
                       ops.default_interpret())
    qs = (q.astype(jnp.float32) * dh ** -0.5).astype(q.dtype)
    qg = qs.reshape(B, T, Hkv, G, dh).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)                       # (B, Hkv, T, dh)
    vg = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kern))(qg, kg, vg)         # (B, Hkv, G, T, dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, dh)
