"""Causal-LM heads over the transformer composer: loss, prefill, decode."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import transformer as T

__all__ = ["lm_loss", "train_metrics", "prefill", "decode_step"]


def lm_loss(params: dict, batch: dict, cfg: T.ModelConfig
            ) -> Tuple[jax.Array, dict]:
    """Next-token cross-entropy.  batch: {tokens|embeds, labels, [mask],
    [positions]}.  labels align with inputs (already shifted by the data
    pipeline).  Returns (loss, metrics)."""
    kw = {}
    if cfg.input_kind == "tokens":
        kw["tokens"] = batch["tokens"]
    else:
        kw["embeds"] = batch["embeds"]
    logits, _, aux = T.forward(params, cfg, positions=batch.get("positions"),
                               **kw)
    labels = batch["labels"]
    with obs.scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    loss = ce + cfg.moe_aux_coef * aux["aux"]
    metrics = {"loss": loss, "ce": ce, **aux,
               "ppl_proxy": jnp.exp(jnp.clip(ce, max=20.0)),
               # mask weight of this batch: lets gradient accumulation
               # recover the global masked mean from per-microbatch means
               # (train/step.py averages ce weighted by ce_weight).
               "ce_weight": denom}
    return loss, metrics


def train_metrics(metrics: dict) -> dict:
    return {k: float(v) for k, v in metrics.items()}


def prefill(params: dict, cfg: T.ModelConfig, *, max_len: int,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            cache_dtype=jnp.bfloat16,
            length: Optional[jax.Array] = None):
    """Run the prompt through the model and build a decode-ready cache.

    Attention-only stacks take the chunked path: ONE token-parallel forward
    (chunked causal attention) that also writes K/V into the cache —
    O(T^2/chunk) attention work instead of the O(T)-sequential
    decode-replay scan.  ``length`` (scalar or per-row ``(B,)``) gives true
    prompt lengths when the batch is right-padded to a common bucket
    length; last-position logits are gathered at ``length - 1`` per row and
    windowed ring caches only fill real positions.

    Stacks with SSM mixers (mamba2/zamba2 hybrids) keep the exact
    decode-replay ``lax.scan`` (SSM caches are strictly single-token);
    ``length`` is unsupported there.
    """
    if tokens is not None:
        B, T_len = tokens.shape
    else:
        B, T_len = embeds.shape[:2]
    cache = T.init_cache(B, max_len, cfg, cache_dtype)
    attn_only = all(s.mixer == "attn" for s in cfg.layers)

    if attn_only:
        kw = {"tokens": tokens} if tokens is not None else {"embeds": embeds}
        logits, cache, _ = T.forward(
            params, cfg, cache=cache,
            cache_index=jnp.asarray(0, jnp.int32),
            fill_len=length, **kw)
        if length is None:
            return logits[:, -1], cache
        last = jnp.broadcast_to(jnp.asarray(length), (B,)) - 1
        out = jnp.take_along_axis(logits, last[:, None, None], axis=1)
        return out[:, 0], cache

    if length is not None:
        raise NotImplementedError(
            "per-row prompt lengths need an attention-only stack "
            "(SSM caches prefill via the sequential scan)")

    def step(carry, t):
        cache = carry
        if tokens is not None:
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, cache, _ = T.forward(params, cfg, tokens=tok,
                                         cache=cache, cache_index=t)
        else:
            emb = jax.lax.dynamic_slice_in_dim(embeds, t, 1, axis=1)
            logits, cache, _ = T.forward(params, cfg, embeds=emb,
                                         cache=cache, cache_index=t)
        return cache, logits[:, 0]

    cache, logits_all = jax.lax.scan(step, cache, jnp.arange(T_len))
    return logits_all[-1], cache


def decode_step(params: dict, cfg: T.ModelConfig, token: jax.Array,
                cache, cache_index: jax.Array):
    """One-token decode.  token: (B,) int32 -> (logits (B, V), new cache)."""
    logits, cache, _ = T.forward(params, cfg, tokens=token[:, None],
                                 cache=cache, cache_index=cache_index)
    return logits[:, 0], cache
