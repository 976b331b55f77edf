"""Dropless expert routing (``layers/moe.py``): no token is dropped under
any imbalance, the shared expert reaches every token, the counters count
what the dispatch did, and the SMOKE qwen3-moe model's loss, gradients,
prefill and decode match the benchmark's plain reference
(``bench/configs/qwen3_moe_reference.py``) on the benchmark's seeded
weights."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.layers.ffn import ffn_apply
from repro.layers.moe import MoEConfig, dispatch_plan, init_moe, moe_apply
from repro.models import causal_lm as LM
from repro.models import transformer as T

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

KEY = jax.random.PRNGKey(0)


def _per_token(params, x, cfg):
    """Each token through its top-k experts one at a time, in f32."""
    xt = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(jnp.asarray(xt) @ params["router"], axis=-1)
    out = []
    for n in range(xt.shape[0]):
        v, i = jax.lax.top_k(probs[n], cfg.top_k)
        g = v / jnp.sum(v)
        acc = jnp.zeros((cfg.d_model,), jnp.float32)
        for j in range(cfg.top_k):
            pe = jax.tree.map(lambda a: a[i[j]], params["experts"])
            acc = acc + g[j] * ffn_apply(pe, jnp.asarray(xt[n:n + 1]),
                                         cfg.expert_ffn)[0]
        out.append(acc)
    return jnp.stack(out).reshape(x.shape)


def _imbalanced(cfg):
    """Params and inputs that send every token to experts 0..k-1."""
    p = init_moe(KEY, cfg)
    x = 1.0 + jnp.abs(jax.random.normal(KEY, (2, 16, cfg.d_model)))
    rank = jnp.arange(cfg.n_experts, dtype=jnp.float32)
    col = jnp.where(rank < cfg.top_k, 1.0 - 0.01 * rank, -1.0)
    p["router"] = jnp.broadcast_to(col, p["router"].shape) / cfg.d_model
    return p, x


@pytest.mark.parametrize("impl", ["dense", "spm_general"])
def test_planted_imbalance_drops_no_token(impl):
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=16, top_k=8,
                    linear_impl=impl)
    p, x = _imbalanced(cfg)
    y, st = moe_apply(p, x, cfg)
    # all 32 tokens on 8 of 16 experts: a capacity of 1.25 x 8 x 32 / 16
    # = 20 rows per expert would have dropped 12 of each expert's 32
    np.testing.assert_array_equal(np.asarray(st["frac"])[:8], 1.0)
    np.testing.assert_array_equal(np.asarray(st["frac"])[8:], 0.0)
    np.testing.assert_allclose(y, _per_token(p, x, cfg), rtol=2e-5,
                               atol=2e-6)


def test_shared_expert_reaches_every_token():
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=1,
                    shared_d_ff=32)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (1, 32, 16))
    y, _ = moe_apply(p, x, cfg)
    y0, _ = moe_apply(p, x, dataclasses.replace(cfg, shared_d_ff=0))
    np.testing.assert_allclose(
        y - y0, ffn_apply(p["shared"], x, cfg.shared_ffn), atol=1e-5)
    assert bool(jnp.all(jnp.any(jnp.abs(y - y0) > 1e-6, axis=-1)))


def test_dispatch_plan_by_hand():
    # experts of 7 assignments over E = 3 at tile 2: groups of 3, 1, 3
    # rows pad to 4, 2, 4 in a buffer of ceil((7 + 3) / 2) = 5 tiles
    e = jnp.array([2, 0, 0, 1, 2, 0, 2], jnp.int32)
    plan = dispatch_plan(e, 3, 2)
    np.testing.assert_array_equal(plan["counts"], [3, 1, 3])
    np.testing.assert_array_equal(plan["dest"], [6, 0, 1, 4, 7, 2, 8])
    np.testing.assert_array_equal(plan["src"],
                                  [1, 2, 5, 7, 3, 7, 0, 4, 6, 7])
    np.testing.assert_array_equal(plan["tile_expert"], [0, 0, 1, 2, 2])


def test_counters_equal_hand_counts():
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=16, top_k=8)
    p, x = _imbalanced(cfg)
    _, st = moe_apply(p, x, cfg)
    N, t = 32, cfg.tile_rows(32)           # mean load 16 rows: tile 16
    buffer = -(-(N * 8 + 16 * (t - 1)) // t) * t
    assert t == 16 and float(st["rows"]) == N * 8
    assert float(st["pad_rows"]) == 0.0    # 32 rows each: whole tiles
    assert float(st["buffer_rows"]) == buffer
    assert float(st["load_max"]) == N / (N * 8 / 16)
    # through the model: summed over layers, worst layer, padded share
    mcfg = get_smoke("qwen3-moe-30b-a3b")
    params = T.init_model(KEY, mcfg)
    tok = jax.random.randint(KEY, (2, 9), 0, mcfg.vocab_size)
    _, m = LM.lm_loss(params, {"tokens": tok[:, :-1],
                               "labels": tok[:, 1:]}, mcfg)
    assert float(m["moe_rows"]) == 2 * 8 * mcfg.top_k * mcfg.n_layers
    assert 0.0 <= float(m["moe_pad_share"]) < 1.0
    assert float(m["moe_load_max"]) >= 1.0


@pytest.mark.parametrize("form", ["per_layer", "hf"])
def test_aux_loss_forms_over_layers(form):
    """``per_layer`` sums each layer's Switch-form loss (HF's over
    ``top_k``); ``hf`` is HF's ``load_balancing_loss_func`` over all
    layers' tokens: ``E * sum_e mean(mask_e) * mean(prob_e)``."""
    cfg = dataclasses.replace(get_smoke("qwen3-moe-30b-a3b"), moe_aux=form)
    mcfg = cfg.moe_cfg()
    p = init_moe(KEY, mcfg)
    E, k = mcfg.n_experts, mcfg.top_k
    acc, frac, prob, switch = T._stats_init(cfg), 0.0, 0.0, 0.0
    for i in range(cfg.n_layers):
        x = jax.random.normal(jax.random.PRNGKey(i + 1), (2, 8, mcfg.d_model))
        _, st = moe_apply(p, x, mcfg)
        acc = T._stats_add(acc, st)
        probs = jax.nn.softmax(x.reshape(-1, mcfg.d_model) @ p["router"], -1)
        mask = jnp.sum(jax.nn.one_hot(jax.lax.top_k(probs, k)[1], E), 1)
        frac += jnp.mean(mask, 0) / cfg.n_layers
        prob += jnp.mean(probs, 0) / cfg.n_layers
        switch += E / k * jnp.sum(jnp.mean(mask, 0) * jnp.mean(probs, 0))
    want = E * jnp.sum(frac * prob) if form == "hf" else switch
    np.testing.assert_allclose(float(T._stats_summary(cfg, acc)["aux"]),
                               float(want), rtol=1e-5)


def test_expert_tiles_run_the_fused_kernel_under_vmap():
    """The vmapped SPM kernel (interpret mode off-TPU) gives the XLA
    composition's outputs and gradients."""
    base = MoEConfig(d_model=128, d_ff=64, n_experts=4, top_k=2,
                     linear_impl="spm_general",
                     spm_backward="custom", spm_use_kernel=False)
    kern = dataclasses.replace(base, spm_use_kernel=True)
    p = init_moe(KEY, base)
    x = jax.random.normal(KEY, (1, 24, 128))
    loss = lambda p, c: jnp.sum(moe_apply(p, x, c)[0] ** 2)
    np.testing.assert_allclose(moe_apply(p, x, kern)[0],
                               moe_apply(p, x, base)[0], rtol=1e-4,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.grad(loss)(p, kern)),
                    jax.tree.leaves(jax.grad(loss)(p, base))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the SMOKE model against the benchmark's plain reference
# ---------------------------------------------------------------------------

def _reference():
    import spec
    return spec.load_module(os.path.join(BENCH, "configs",
                                         "qwen3_moe_reference.py"))


def _shape(cfg):
    return {"hidden_size": cfg.d_model, "intermediate_size": 0,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim,
            "tie_word_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "moe_intermediate_size": cfg.moe_d_ff, "norm_topk_prob": True,
            "router_aux_loss_coef": cfg.moe_aux_coef}


@pytest.fixture(scope="module")
def smoke():
    import weights
    cfg = dataclasses.replace(get_smoke("qwen3-moe-30b-a3b"),
                              tie_embeddings=False)
    st = jax.eval_shape(lambda: T.init_model(KEY, cfg))
    return cfg, weights.make_params(st, 1234)


def test_smoke_loss_and_grads_match_reference(smoke):
    cfg, p = smoke
    tok = jax.random.randint(jax.random.PRNGKey(5), (2, 17), 0,
                             cfg.vocab_size)
    b = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    (loss, m), g = jax.value_and_grad(
        lambda p: LM.lm_loss(p, b, cfg), has_aux=True)(p)
    ref_loss, ref_g = _reference().loss_and_grads(
        _shape(cfg), "f32", p, b["tokens"], b["labels"])
    assert float(m["aux"]) > 1.0           # top_k at perfect balance: 2
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for a, r in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        assert float(jnp.linalg.norm(a - r)) <= 1e-5 * float(
            jnp.linalg.norm(r)) + 1e-9


def test_smoke_prefill_then_decode_matches_reference(smoke):
    cfg, p = smoke
    P, steps = 11, 4
    toks = jax.random.randint(jax.random.PRNGKey(9), (1, P + steps), 0,
                              cfg.vocab_size)
    logits, cache = LM.prefill(p, cfg, max_len=32, tokens=toks[:, :P],
                               cache_dtype=jnp.float32)
    got = [logits[0]]
    for i in range(steps):
        logits, cache = LM.decode_step(p, cfg, toks[:, P + i], cache,
                                       jnp.asarray(P + i, jnp.int32))
        got.append(logits[0])
    want = _reference().logits_at(_shape(cfg), "f32", p, toks[0],
                                  jnp.arange(P - 1, P + steps))
    np.testing.assert_allclose(jnp.stack(got), want, rtol=1e-4, atol=1e-4)
