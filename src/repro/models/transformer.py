"""Block-pattern transformer composer.

One ``ModelConfig`` describes any of the assigned architectures: a tuple of
``LayerSpec`` (mixer = attention / mamba / +shared block, mlp = dense /
moe / none), GQA geometry, RoPE flavor, MoE and SSM hyperparameters, and —
central to this repo — the ``linear_impl`` knob that swaps every projection
between dense and SPM (paper §7).

Layers are scanned over repeating pattern groups (``scan_group``) so HLO
size stays O(1) in depth; heterogeneous stacks (zamba2's shared-attention
interleave) unroll.  The same ``forward`` serves training (cache=None),
prefill, and single-token decode (cache + cache_index).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.layers.attention import (AttentionConfig, attention_apply,
                                    init_attention, init_kv_cache)
from repro.layers.embedding import (EmbeddingConfig, embed, init_embedding,
                                    unembed)
from repro.layers.ffn import FFNConfig, ffn_block_apply, init_ffn
from repro.layers.mamba2 import (Mamba2Config, init_mamba2, init_ssm_cache,
                                 mamba2_apply)
from repro.layers.moe import MoEConfig, init_moe, moe_apply
from repro.layers.norms import init_rms_norm, rms_norm
from repro.layers.rope import mrope_angles, rope_angles
from repro.parallel.ctx import constrain

__all__ = ["LayerSpec", "ModelConfig", "init_model", "forward",
           "init_cache", "model_param_count"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"              # "attn" | "mamba"
    mlp: str = "dense"               # "dense" | "moe" | "none"
    window: Optional[int] = None     # sliding window for attn mixers
    rope: str = "default"            # rope table key: "default" | "local"
    shared_block: bool = False       # apply the shared attn+ffn block first


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    layers: Tuple[LayerSpec, ...]
    scan_group: int = 1              # 0 = unrolled; else pattern period
    # attention
    qk_norm: bool = False
    rope_theta: float = 1e4
    rope_local_theta: float = 1e4
    rope_kind: str = "default"       # "default" | "mrope"
    mrope_sections: Tuple[int, ...] = (16, 24, 24)
    q_chunk: int = 512
    k_chunk: int = 1024
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    # ssm
    ssm_state: int = 0
    ssm_head: int = 64
    ssm_chunk: int = 128
    # shared block (zamba2)
    shared_attn_d_ff: int = 0
    # paper knob
    linear_impl: str = "dense"
    spm_stages: Optional[int] = None
    spm_backward: str = "custom"
    spm_use_kernel: Optional[bool] = None  # fused Pallas operator (tri-state:
                                           # None=auto/on-TPU, True, False)
    spm_schedule: str = "butterfly"        # "two_level" + spm_n_shards > 1:
    spm_n_shards: int = 1                  # feature axis distributable over
                                           # the "model" mesh axis via
                                           # parallel/spm_shard.py
    spm_overlap: Optional[bool] = None     # overlap-scheduled sharded
                                           # executor (row-block pipelined
                                           # exchanges): None=auto/on-TPU,
                                           # True=force, False=off
    spm_quant_acts: bool = False           # int8 activation I/O on the fused
                                           # kernel path (per-block scales)
    spm_quant_coeffs: bool = False         # int8 per-stage-scaled coefficient
                                           # tables dequantized in VMEM
    ffn_activation: str = "swiglu"         # "swiglu" (gated) or an ungated
                                           # "relu"/"silu"/"gelu" — the
                                           # shapes the residual-block
                                           # megakernel can fuse
    spm_block_fuse: Optional[bool] = None  # residual-block megakernel
                                           # (norm -> SPM -> act -> SPM ->
                                           # residual in one Pallas chain):
                                           # None=auto/on-TPU, True=force
                                           # (interpret off-TPU), False=off
    compress_pod_grads: bool = False       # int8 error-feedback pod-DP grad
                                           # reduction (train/step.py
                                           # make_pod_train_step)
    # io
    input_kind: str = "tokens"       # "tokens" | "embeddings"
    tie_embeddings: bool = True
    embed_scale: float = 1.0
    embed_onehot: bool = False       # matmul-lowered lookup (sharded vocab)
    logits_dtype: Any = "float32"    # bf16 halves LM-head HBM traffic
                                     # (softmax stats still f32 in-regs)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    # ---- derived sub-configs -------------------------------------------
    def attn_cfg(self, spec: LayerSpec) -> AttentionConfig:
        return AttentionConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            use_qk_norm=self.qk_norm, window=spec.window,
            linear_impl=self.linear_impl, spm_stages=self.spm_stages,
            spm_backward=self.spm_backward,
            spm_use_kernel=self.spm_use_kernel,
            spm_schedule=self.spm_schedule, spm_n_shards=self.spm_n_shards,
            spm_overlap=self.spm_overlap,
            spm_quant_acts=self.spm_quant_acts,
            spm_quant_coeffs=self.spm_quant_coeffs,
            spm_block_fuse=self.spm_block_fuse,
            q_chunk=self.q_chunk,
            k_chunk=self.k_chunk, param_dtype=self.param_dtype)

    def ffn_cfg(self) -> FFNConfig:
        return FFNConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            linear_impl=self.linear_impl,
            activation=self.ffn_activation, spm_stages=self.spm_stages,
            spm_backward=self.spm_backward,
            spm_use_kernel=self.spm_use_kernel,
            spm_schedule=self.spm_schedule, spm_n_shards=self.spm_n_shards,
            spm_overlap=self.spm_overlap,
            spm_quant_acts=self.spm_quant_acts,
            spm_quant_coeffs=self.spm_quant_coeffs,
            spm_block_fuse=self.spm_block_fuse,
            param_dtype=self.param_dtype)

    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff=self.moe_d_ff,
            n_experts=self.n_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            shared_d_ff=self.shared_d_ff, linear_impl=self.linear_impl,
            spm_stages=self.spm_stages, spm_backward=self.spm_backward,
            spm_use_kernel=self.spm_use_kernel,
            spm_schedule=self.spm_schedule, spm_n_shards=self.spm_n_shards,
            spm_overlap=self.spm_overlap,
            spm_quant_acts=self.spm_quant_acts,
            spm_quant_coeffs=self.spm_quant_coeffs,
            param_dtype=self.param_dtype)

    def mamba_cfg(self) -> Mamba2Config:
        return Mamba2Config(
            d_model=self.d_model, d_state=self.ssm_state,
            d_head=self.ssm_head, chunk=self.ssm_chunk,
            linear_impl=self.linear_impl, spm_stages=self.spm_stages,
            spm_backward=self.spm_backward,
            spm_use_kernel=self.spm_use_kernel,
            spm_schedule=self.spm_schedule, spm_n_shards=self.spm_n_shards,
            spm_overlap=self.spm_overlap,
            spm_quant_acts=self.spm_quant_acts,
            spm_quant_coeffs=self.spm_quant_coeffs,
            param_dtype=self.param_dtype)

    def shared_attn_cfg(self) -> AttentionConfig:
        return self.attn_cfg(LayerSpec(mixer="attn"))

    def shared_ffn_cfg(self) -> FFNConfig:
        return FFNConfig(
            d_model=self.d_model, d_ff=self.shared_attn_d_ff,
            linear_impl=self.linear_impl,
            activation=self.ffn_activation, spm_stages=self.spm_stages,
            spm_backward=self.spm_backward,
            spm_use_kernel=self.spm_use_kernel,
            spm_schedule=self.spm_schedule, spm_n_shards=self.spm_n_shards,
            spm_overlap=self.spm_overlap,
            spm_quant_acts=self.spm_quant_acts,
            spm_quant_coeffs=self.spm_quant_coeffs,
            spm_block_fuse=self.spm_block_fuse,
            param_dtype=self.param_dtype)

    def embed_cfg(self) -> EmbeddingConfig:
        return EmbeddingConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            tie_output=self.tie_embeddings, param_dtype=self.param_dtype)

    # ---- scan layout ----------------------------------------------------
    @property
    def scanned(self) -> bool:
        g = self.scan_group
        if g <= 0 or self.n_layers % g:
            return False
        return all(self.layers[i] == self.layers[i % g]
                   for i in range(self.n_layers))

    @property
    def uniform_ignoring_shared(self) -> bool:
        """Layers identical except for the shared-block flag (zamba2)."""
        base = dataclasses.replace(self.layers[0], shared_block=False)
        return all(dataclasses.replace(s, shared_block=False) == base
                   for s in self.layers)

    @property
    def stacked_params(self) -> bool:
        """Layer params stored stacked (scan-compatible).  Hybrid stacks
        too: shared-block application is a ``lax.cond`` inside the scan
        body (HLO stays O(1) in depth), decode unrolls by slicing."""
        return self.scanned or self.uniform_ignoring_shared

    @property
    def group_specs(self) -> Tuple[LayerSpec, ...]:
        if self.scanned:
            return self.layers[: self.scan_group]
        if self.uniform_ignoring_shared:
            return (dataclasses.replace(self.layers[0], shared_block=False),)
        return self.layers

    @property
    def n_groups(self) -> int:
        if self.scanned:
            return self.n_layers // self.scan_group
        if self.uniform_ignoring_shared:
            return self.n_layers
        return 1

    @property
    def has_shared_block(self) -> bool:
        return any(s.shared_block for s in self.layers)

    @property
    def shared_flags(self) -> Tuple[bool, ...]:
        """Per-group shared-block application flags (hybrid scan path)."""
        return tuple(s.shared_block for s in self.layers)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key: jax.Array, spec: LayerSpec, cfg: ModelConfig) -> dict:
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict = {"norm1": init_rms_norm(cfg.d_model, cfg.param_dtype)}
    if spec.mixer == "attn":
        p["mixer"] = init_attention(km, cfg.attn_cfg(spec))
    elif spec.mixer == "mamba":
        p["mixer"] = init_mamba2(km, cfg.mamba_cfg())
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != "none":
        p["norm2"] = init_rms_norm(cfg.d_model, cfg.param_dtype)
        if spec.mlp == "dense":
            p["mlp"] = init_ffn(kf, cfg.ffn_cfg())
        elif spec.mlp == "moe":
            p["mlp"] = init_moe(kf, cfg.moe_cfg())
        else:
            raise ValueError(spec.mlp)
    return p


def _init_group(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.group_specs))
    return {f"l{i}": _init_layer(keys[i], spec, cfg)
            for i, spec in enumerate(cfg.group_specs)}


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl, ks = jax.random.split(key, 3)
    p: dict = {"final_norm": init_rms_norm(cfg.d_model, cfg.param_dtype)}
    # embeddings-input archs (modality frontend stub) still need the vocab
    # table for the output head.
    p["embed"] = init_embedding(ke, cfg.embed_cfg())
    if cfg.stacked_params:
        gkeys = jax.random.split(kl, cfg.n_groups)
        groups = [_init_group(k, cfg) for k in gkeys]
        p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    else:
        lkeys = jax.random.split(kl, cfg.n_layers)
        p["layers"] = [_init_layer(lkeys[i], cfg.layers[i], cfg)
                       for i in range(cfg.n_layers)]
    if cfg.has_shared_block:
        k1, k2 = jax.random.split(ks)
        p["shared"] = {
            "norm1": init_rms_norm(cfg.d_model, cfg.param_dtype),
            "attn": init_attention(k1, cfg.shared_attn_cfg()),
            "norm2": init_rms_norm(cfg.d_model, cfg.param_dtype),
            "ffn": init_ffn(k2, cfg.shared_ffn_cfg()),
        }
    return p


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _init_layer_cache(batch: int, max_len: int, spec: LayerSpec,
                      cfg: ModelConfig, dtype) -> dict:
    c: dict = {}
    if spec.mixer == "attn":
        c["mixer"] = init_kv_cache(batch, max_len, cfg.attn_cfg(spec), dtype)
    else:
        c["mixer"] = init_ssm_cache(batch, cfg.mamba_cfg(), jnp.float32)
    if spec.shared_block:
        c["shared"] = init_kv_cache(batch, max_len, cfg.shared_attn_cfg(),
                                    dtype)
    return c


def init_cache(batch: int, max_len: int, cfg: ModelConfig,
               dtype=jnp.bfloat16):
    """Decode cache matching the layer layout (stacked when scanned)."""
    if cfg.scanned:
        group = {f"l{i}": _init_layer_cache(batch, max_len, spec, cfg, dtype)
                 for i, spec in enumerate(cfg.group_specs)}
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_groups,) + x.shape).copy(),
            group)
    return [_init_layer_cache(batch, max_len, spec, cfg, dtype)
            for spec in cfg.layers]


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _rope_tables(cfg: ModelConfig, positions: jax.Array) -> dict:
    """positions: (B, T) or (3, B, T) for mrope."""
    if cfg.rope_kind == "mrope":
        cos, sin = mrope_angles(positions, cfg.head_dim,
                                cfg.mrope_sections, cfg.rope_theta)
        return {"default": (cos, sin), "local": (cos, sin)}
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    tables = {"default": (cos, sin)}
    if cfg.rope_local_theta != cfg.rope_theta:
        cl, sl = rope_angles(positions, cfg.head_dim, cfg.rope_local_theta)
        tables["local"] = (cl, sl)
    else:
        tables["local"] = (cos, sin)
    return tables


def _apply_shared(shared_params: dict, h: jax.Array, cfg: ModelConfig,
                  rope: dict, cache, cache_index, fill_len=None):
    cos, sin = rope["default"]
    a, new_cache = attention_apply(
        shared_params["attn"], h,
        cfg.shared_attn_cfg(), cos=cos, sin=sin,
        cache=cache, cache_index=cache_index, fill_len=fill_len,
        norm_params=shared_params["norm1"])
    h = h + a
    return ffn_block_apply(shared_params["ffn"], shared_params["norm2"], h,
                           cfg.shared_ffn_cfg()), new_cache


def _apply_layer(lp: dict, spec: LayerSpec, cfg: ModelConfig, h: jax.Array,
                 rope: dict, shared_params: Optional[dict],
                 cache: Optional[dict], cache_index, fill_len=None):
    new_cache: dict = {}
    aux = jnp.zeros((), jnp.float32)
    if spec.shared_block:
        sc = None if cache is None else cache.get("shared")
        h, nsc = _apply_shared(shared_params, h, cfg, rope, sc, cache_index,
                               fill_len)
        if cache is not None:
            new_cache["shared"] = nsc
    mc = None if cache is None else cache["mixer"]
    if spec.mixer == "attn":
        # pre-attention norm applied INSIDE the layer (norm_params): the
        # fused-qkv path folds it into the projection kernels' prologue,
        # the fallback is bitwise the old rms_norm-then-apply composition.
        cos, sin = rope[spec.rope]
        y, nmc = attention_apply(lp["mixer"], h, cfg.attn_cfg(spec),
                                 cos=cos, sin=sin, cache=mc,
                                 cache_index=cache_index, fill_len=fill_len,
                                 norm_params=lp["norm1"])
    else:
        y, nmc = mamba2_apply(lp["mixer"], rms_norm(lp["norm1"], h),
                              cfg.mamba_cfg(), cache=mc)
    if cache is not None:
        new_cache["mixer"] = nmc
    h = h + y
    if spec.mlp == "dense":
        h = ffn_block_apply(lp["mlp"], lp["norm2"], h, cfg.ffn_cfg())
    elif spec.mlp == "moe":
        y, aux = moe_apply(lp["mlp"], rms_norm(lp["norm2"], h), cfg.moe_cfg())
        h = h + y
    return h, (new_cache if cache is not None else None), aux


def forward(params: dict, cfg: ModelConfig, *,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            cache=None, cache_index=None, fill_len=None):
    """Returns (logits, new_cache, aux_loss).

    Three modes:

    * training — ``cache=None``: plain causal forward over the full batch.
    * chunked prefill — ``cache`` given with ``T > 1`` tokens: one causal
      forward whose attention layers also write K/V into the cache starting
      at ``cache_index`` (attention-only stacks; SSM caches are strictly
      single-token).  ``fill_len`` (scalar or per-row ``(B,)``) gives true
      prompt lengths when the batch is right-padded to a bucket length.
    * decode — ``T == 1`` with ``cache`` + ``cache_index`` (scalar, or
      per-row ``(B,)`` for continuous batching).
    """
    if tokens is not None:
        h = embed(params["embed"], tokens, cfg.embed_cfg(), cfg.dtype,
                  onehot=cfg.embed_onehot)
        B, T = tokens.shape
    else:
        h = embeds.astype(cfg.dtype)
        B, T = embeds.shape[:2]
    if cfg.embed_scale != 1.0:
        h = h * jnp.asarray(cfg.embed_scale, h.dtype)
    # under an activation_sharding(full_batch=True) context: tokens enter
    # replicated over "model" (cheap — int32); pinning the gather OUTPUT
    # model-replicated first makes the vocab-sharded gather lower as
    # mask+all-reduce, and the follow-up full-mesh-DP reshard is a free
    # local slice (EXPERIMENTS §Perf I6).
    h = constrain(h, "btd")
    h = constrain(h, "batch_full")

    if positions is None:
        if cache_index is None:
            base = jnp.arange(T)
        else:
            ci = jnp.asarray(cache_index)
            off = ci[:, None] if ci.ndim == 1 else ci
            base = off + jnp.arange(T)
        positions = jnp.broadcast_to(base, (B, T))
        if cfg.rope_kind == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, T))
    rope = _rope_tables(cfg, positions)

    shared_params = params.get("shared")
    aux_total = jnp.zeros((), jnp.float32)

    use_scan = cfg.scanned or (cfg.uniform_ignoring_shared
                               and cache is None)
    if use_scan:
        specs = cfg.group_specs
        hybrid = cfg.has_shared_block and not cfg.scanned

        def group_body(carry, xs):
            h, aux = carry
            if hybrid:
                if cache is None:
                    gp, flag = xs
                    gc = {f"l{i}": None for i in range(len(specs))}
                else:
                    gp, gc, flag = xs
                # shared attn+ffn applied only at flagged groups; lax.cond
                # keeps the shared block compiled ONCE for all depths.
                h = jax.lax.cond(
                    flag,
                    lambda hh: _apply_shared(shared_params, hh, cfg, rope,
                                             None, cache_index)[0],
                    lambda hh: hh, h)
            else:
                if cache is None:
                    gp = xs
                    gc = {f"l{i}": None for i in range(len(specs))}
                else:
                    gp, gc = xs
            new_gc = {}
            for i, spec in enumerate(specs):
                h, nc, a = _apply_layer(gp[f"l{i}"], spec, cfg, h, rope,
                                        shared_params, gc[f"l{i}"],
                                        cache_index, fill_len)
                new_gc[f"l{i}"] = nc
                aux = aux + a
            out = None if cache is None else new_gc
            return (h, aux), out

        body = group_body
        if cfg.remat and cache is None:
            body = jax.checkpoint(group_body, prevent_cse=False)
        xs = [params["layers"]]
        if cache is not None:
            xs.append(cache)
        if hybrid:
            xs.append(jnp.asarray(cfg.shared_flags))
        xs = tuple(xs) if len(xs) > 1 else xs[0]
        (h, aux_total), new_cache = jax.lax.scan(body, (h, aux_total), xs)
    else:
        new_cache = [] if cache is not None else None
        stacked = cfg.stacked_params
        for i, spec in enumerate(cfg.layers):
            if stacked:
                lp = jax.tree.map(lambda x: x[i], params["layers"])["l0"]
            else:
                lp = params["layers"][i]
            lc = None if cache is None else cache[i]
            step = _apply_layer
            if cfg.remat and cache is None:
                step = jax.checkpoint(_apply_layer,
                                      static_argnums=(1, 2), prevent_cse=False)
            h, nc, a = step(lp, spec, cfg, h, rope, shared_params, lc,
                            cache_index, fill_len)
            aux_total = aux_total + a
            if cache is not None:
                new_cache.append(nc)

    with obs.scope("lm_head"):
        h = rms_norm(params["final_norm"], h)
        logits = unembed(params["embed"], h.astype(cfg.logits_dtype),
                         cfg.embed_cfg())
    return logits, new_cache, aux_total


def model_param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
