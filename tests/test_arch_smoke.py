"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train step on CPU, asserting shapes + no NaNs (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, arch_shapes, get_config, get_smoke
from repro.models import causal_lm as LM
from repro.models import transformer as T
from repro.optim.adamw import OptimizerConfig
from repro.train import make_train_state, make_train_step

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def _batch(cfg):
    b = {"labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.input_kind == "tokens":
        b["tokens"] = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    else:
        b["embeds"] = jax.random.normal(KEY, (B, S, cfg.d_model))
        if cfg.rope_kind == "mrope":
            pos = jnp.broadcast_to(jnp.arange(S), (B, S))
            b["positions"] = jnp.broadcast_to(pos, (3, B, S))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke(arch)
    params = T.init_model(KEY, cfg)
    batch = _batch(cfg)
    kw = ({"tokens": batch["tokens"]} if cfg.input_kind == "tokens"
          else {"embeds": batch["embeds"],
                "positions": batch.get("positions")})
    logits, _, aux = T.forward(params, cfg, **kw)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert all(bool(jnp.isfinite(v)) for v in aux.values())

    step = make_train_step(lambda p, b: LM.lm_loss(p, b, cfg),
                           OptimizerConfig(lr=1e-3, total_steps=10))
    state = make_train_state(params)
    state2, metrics = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["skipped"]) == 0.0
    # params actually moved
    d0 = jax.tree.leaves(state["params"])[0]
    d1 = jax.tree.leaves(state2["params"])[0]
    assert not np.allclose(d0, d1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters (they are
    exercised via the dry-run only — ShapeDtypeStruct, no allocation)."""
    cfg = get_config(arch)
    expected = {
        "zamba2-1.2b": (38, 2048, 32, 32, 0, 32000),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 0, 151936),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 0, 202048),
        "mamba2-370m": (48, 1024, 16, 16, 0, 50280),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (got, expected)
    if arch == "qwen3-moe-30b-a3b":
        assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (128, 8, 768)
    if arch == "llama4-scout-17b-a16e":
        assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (16, 1, 8192)
    if arch == "zamba2-1.2b":
        assert cfg.ssm_state == 64 and cfg.shared_attn_d_ff == 8192
    if arch == "mamba2-370m":
        assert cfg.ssm_state == 128
    if arch == "gemma3-12b":
        # 5:1 local:global
        w = [s.window for s in cfg.layers[:6]]
        assert w == [1024] * 5 + [None]


def test_shape_assignment_gating():
    """long_500k runs only for sub-quadratic archs (DESIGN §4)."""
    for arch in ARCH_IDS:
        names = [s.name for s in arch_shapes(arch)]
        if arch in ("zamba2-1.2b", "gemma3-12b", "mamba2-370m"):
            assert "long_500k" in names
        else:
            assert "long_500k" not in names
        for required in ("train_4k", "prefill_32k", "decode_32k"):
            assert required in names


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-370m",
                                  "zamba2-1.2b"])
def test_smoke_decode_matches_forward(arch):
    """Prefill+decode path == cache-free forward on the smoke config."""
    cfg = get_smoke(arch)
    if cfg.input_kind != "tokens":
        pytest.skip("token archs only")
    params = T.init_model(KEY, cfg)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _, _ = T.forward(params, cfg, tokens=toks)
    last, cache = LM.prefill(params, cfg, max_len=S, tokens=toks,
                             cache_dtype=jnp.float32)
    np.testing.assert_allclose(last, full[:, -1], atol=3e-2, rtol=3e-2)
