"""Layer-level tests: attention (chunked == naive, decode == full),
mamba2 (chunked SSD == sequential recurrence), MoE, GRU (paper §6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers import (AttentionConfig, FFNConfig, GRUConfig,
                          Mamba2Config, MoEConfig, attention_apply,
                          chunked_causal_attention, ffn_apply, gru_apply,
                          gru_cell, init_attention, init_ffn, init_gru,
                          init_kv_cache, init_moe, init_mamba2,
                          init_ssm_cache, mamba2_apply, moe_apply)
from repro.layers.rope import apply_rope, mrope_angles, rope_angles

KEY = jax.random.PRNGKey(0)


def naive_attention(q, k, v, window=None):
    B, T, H, dh = q.shape
    G = H // k.shape[2]
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) / dh ** 0.5
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    m = j <= i
    if window is not None:
        m &= j > i - window
    s = jnp.where(m[None, None], s, -1e30)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vv)


@pytest.mark.parametrize("H,Hkv,window,qc,kc", [
    (8, 8, None, 16, 16),     # MHA
    (8, 4, None, 16, 8),      # GQA 2:1
    (8, 2, None, 13, 9),      # GQA 4:1, ragged chunks
    (8, 4, 8, 16, 16),        # sliding window
])
def test_chunked_attention_matches_naive(H, Hkv, window, qc, kc):
    B, T, dh = 2, 64, 16
    q = jax.random.normal(KEY, (B, T, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, dh))
    out = chunked_causal_attention(q, k, v, window=window, q_chunk=qc,
                                   k_chunk=kc)
    np.testing.assert_allclose(out, naive_attention(q, k, v, window),
                               atol=1e-4)


@pytest.mark.parametrize("T,qc,kc,window", [
    (61, 16, 16, None),   # prime T: edge chunks are padded + masked
    (61, 16, 16, 8),      # prime T, sliding window
    (37, 13, 11, None),   # odd T, odd ragged chunks
    (53, 64, 64, None),   # chunk larger than T
])
def test_chunked_attention_odd_lengths_match_naive(T, qc, kc, window):
    """Regression: prime/odd T used to degrade to chunk=1 (the largest
    chunk divisor of 61 is 1 — a length-61 scan of single-row chunks).
    The edge chunk is now padded and masked instead; padded keys must
    never leak into real queries nor padded queries into the output."""
    B, H, Hkv, dh = 2, 4, 2, 16
    q = jax.random.normal(KEY, (B, T, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, dh))
    out = chunked_causal_attention(q, k, v, window=window, q_chunk=qc,
                                   k_chunk=kc)
    np.testing.assert_allclose(out, naive_attention(q, k, v, window),
                               atol=1e-4)


@pytest.mark.parametrize("window", [None, 8])
def test_attention_decode_matches_full(window):
    B, T, d = 2, 32, 64
    cfg = AttentionConfig(d_model=d, n_heads=8, n_kv_heads=4, head_dim=8,
                          use_qk_norm=True, window=window, q_chunk=8,
                          k_chunk=8)
    p = init_attention(KEY, cfg)
    x = jax.random.normal(KEY, (B, T, d))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    cos, sin = rope_angles(pos, cfg.head_dim)
    y_full, _ = attention_apply(p, x, cfg, cos=cos, sin=sin)
    cache = init_kv_cache(B, T, cfg, jnp.float32)
    outs = []
    for t in range(T):
        ct, st = rope_angles(jnp.full((B, 1), t), cfg.head_dim)
        yt, cache = attention_apply(p, x[:, t:t + 1], cfg, cos=ct, sin=st,
                                    cache=cache, cache_index=jnp.array(t))
        outs.append(yt)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), y_full, atol=2e-3)


def test_windowed_cache_is_ring_buffer():
    cfg = AttentionConfig(d_model=16, n_heads=2, n_kv_heads=2, head_dim=8,
                          window=4)
    cache = init_kv_cache(3, 1000, cfg)
    assert cache["k"].shape == (3, 4, 2, 8)     # window, not max_len


def test_windowed_ring_wraparound_decode():
    """Decode past the window (T=11 steps, window=4): once the ring wraps
    (t >= window) every step must still reproduce the full windowed
    forward — a wrong slot/age mask only shows up AFTER wraparound."""
    B, T, d, W = 2, 11, 16, 4
    cfg = AttentionConfig(d_model=d, n_heads=2, n_kv_heads=2, head_dim=8,
                          window=W)
    p = init_attention(KEY, cfg)
    x = jax.random.normal(KEY, (B, T, d))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    cos, sin = rope_angles(pos, cfg.head_dim)
    y_full, _ = attention_apply(p, x, cfg, cos=cos, sin=sin)
    cache = init_kv_cache(B, T, cfg, jnp.float32)
    for t in range(T):
        ct, st = rope_angles(jnp.full((B, 1), t), cfg.head_dim)
        yt, cache = attention_apply(p, x[:, t:t + 1], cfg, cos=ct, sin=st,
                                    cache=cache, cache_index=jnp.array(t))
        np.testing.assert_allclose(yt[:, 0], y_full[:, t], atol=2e-3,
                                   err_msg=f"step {t} (wrapped: {t >= W})")


@pytest.mark.parametrize("window", [None, 4])
def test_decode_per_row_cache_index_matches_scalar(window):
    """A (B,) cache_index with every row at the same position is bitwise
    the scalar path: the continuous-batching scatter-write and the
    fixed-batch dynamic_update_slice must agree exactly."""
    B, T, d = 2, 6, 16
    cfg = AttentionConfig(d_model=d, n_heads=2, n_kv_heads=2, head_dim=8,
                          window=window)
    p = init_attention(KEY, cfg)
    x = jax.random.normal(KEY, (B, T, d))
    c_s = init_kv_cache(B, T, cfg, jnp.float32)
    c_r = init_kv_cache(B, T, cfg, jnp.float32)
    for t in range(T):
        ct, st = rope_angles(jnp.full((B, 1), t), cfg.head_dim)
        ys, c_s = attention_apply(p, x[:, t:t + 1], cfg, cos=ct, sin=st,
                                  cache=c_s, cache_index=jnp.array(t))
        yr, c_r = attention_apply(p, x[:, t:t + 1], cfg, cos=ct, sin=st,
                                  cache=c_r,
                                  cache_index=jnp.full((B,), t, jnp.int32))
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yr))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("window", [None, 4])
def test_prefill_into_cache_matches_sequential_decode(window):
    """Block prefill of a right-padded batch (per-row fill_len), then one
    per-row decode step == each row prefilled token-by-token alone.  This
    is the continuous-batching admit path: padded cache slots must stay
    invisible and (windowed) padded keys must not evict real ones."""
    d, Tpad, max_len = 16, 12, 16
    lens = [8, 5]
    cfg = AttentionConfig(d_model=d, n_heads=2, n_kv_heads=2, head_dim=8,
                          window=window)
    p = init_attention(KEY, cfg)
    x = jax.random.normal(KEY, (2, Tpad, d))
    xt = jax.random.normal(jax.random.PRNGKey(3), (2, 1, d))

    # reference: each row alone, sequential decode over its true length
    refs = []
    for r, L in enumerate(lens):
        cache = init_kv_cache(1, max_len, cfg, jnp.float32)
        for t in range(L):
            ct, st = rope_angles(jnp.full((1, 1), t), cfg.head_dim)
            _, cache = attention_apply(p, x[r:r + 1, t:t + 1], cfg, cos=ct,
                                       sin=st, cache=cache,
                                       cache_index=jnp.array(t))
        ct, st = rope_angles(jnp.full((1, 1), L), cfg.head_dim)
        y, _ = attention_apply(p, xt[r:r + 1], cfg, cos=ct, sin=st,
                               cache=cache, cache_index=jnp.array(L))
        refs.append(y[0, 0])

    # batched: ONE chunked prefill over the padded prompts, then a
    # per-row-index decode step at each row's own length
    cache = init_kv_cache(2, max_len, cfg, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(Tpad), (2, Tpad))
    cos, sin = rope_angles(pos, cfg.head_dim)
    _, cache = attention_apply(p, x, cfg, cos=cos, sin=sin, cache=cache,
                               cache_index=jnp.array(0),
                               fill_len=jnp.asarray(lens, jnp.int32))
    ci = jnp.asarray(lens, jnp.int32)
    ct, st = rope_angles(ci[:, None], cfg.head_dim)
    y, _ = attention_apply(p, xt, cfg, cos=ct, sin=st, cache=cache,
                           cache_index=ci)
    np.testing.assert_allclose(y[0, 0], refs[0], atol=2e-3)
    np.testing.assert_allclose(y[1, 0], refs[1], atol=2e-3)


def test_mrope_reduces_to_rope_for_text():
    """When (t, h, w) ids coincide, M-RoPE == 1-D RoPE (paper-of-record
    behaviour for text tokens)."""
    T, dh = 16, 16
    pos1 = jnp.broadcast_to(jnp.arange(T), (2, T))
    pos3 = jnp.broadcast_to(pos1, (3, 2, T))
    c1, s1 = rope_angles(pos1, dh)
    c3, s3 = mrope_angles(pos3, dh, (2, 3, 3))
    x = jax.random.normal(KEY, (2, T, 4, dh))
    np.testing.assert_allclose(apply_rope(x, c1, s1), apply_rope(x, c3, s3),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# mamba2 / SSD
# ---------------------------------------------------------------------------

def test_ssd_chunked_matches_sequential():
    """Chunked SSD (train path) == step-by-step recurrence (decode path)."""
    cfg = Mamba2Config(d_model=32, d_state=16, d_head=8, chunk=8)
    p = init_mamba2(KEY, cfg)
    x = 0.5 * jax.random.normal(KEY, (2, 32, 32))
    y_train, _ = mamba2_apply(p, x, cfg)
    cache = init_ssm_cache(2, cfg)
    outs = []
    for t in range(32):
        yt, cache = mamba2_apply(p, x[:, t:t + 1], cfg, cache=cache)
        outs.append(yt)
    np.testing.assert_allclose(y_train, jnp.concatenate(outs, 1), atol=2e-3)


def test_ssd_chunk_size_invariance():
    cfg8 = Mamba2Config(d_model=32, d_state=16, d_head=8, chunk=8)
    cfg16 = Mamba2Config(d_model=32, d_state=16, d_head=8, chunk=16)
    p = init_mamba2(KEY, cfg8)
    x = 0.5 * jax.random.normal(KEY, (2, 32, 32))
    y8, _ = mamba2_apply(p, x, cfg8)
    y16, _ = mamba2_apply(p, x, cfg16)
    np.testing.assert_allclose(y8, y16, atol=1e-3)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_routes_and_balances():
    from repro.layers.moe import load_balancing_loss
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=2)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, 16))
    y, st = moe_apply(p, x, cfg)
    assert y.shape == x.shape
    aux = load_balancing_loss(st["frac"], st["prob"])
    assert bool(jnp.isfinite(aux)) and float(aux) > 1.0   # ~top_k balanced
    g = jax.grad(lambda p: jnp.sum(moe_apply(p, x, cfg)[0] ** 2))(p)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in jax.tree.leaves(g))



# ---------------------------------------------------------------------------
# GRU (paper §6)
# ---------------------------------------------------------------------------

def test_gru_cell_matches_paper_equations():
    """Dense GRU cell == explicit eqs. 20–23."""
    cfg = GRUConfig(d_in=8, d_hidden=8, linear_impl="dense")
    p = init_gru(KEY, cfg)
    x = jax.random.normal(KEY, (3, 8))
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    got = gru_cell(p, x, h, cfg)
    z = jax.nn.sigmoid(x @ p["wz"]["w"] + p["wz"]["b"] + h @ p["uz"]["w"])
    r = jax.nn.sigmoid(x @ p["wr"]["w"] + p["wr"]["b"] + h @ p["ur"]["w"])
    ht = jnp.tanh(x @ p["wh"]["w"] + p["wh"]["b"] + (r * h) @ p["uh"]["w"])
    want = (1 - z) * h + z * ht
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_spm_gru_preserves_semantics_and_trains():
    cfg = GRUConfig(d_in=16, d_hidden=16, linear_impl="spm_rotation")
    p = init_gru(KEY, cfg)
    x = jax.random.normal(KEY, (2, 12, 16))
    hs, hT = gru_apply(p, x, cfg)
    assert hs.shape == (2, 12, 16) and hT.shape == (2, 16)
    g = jax.grad(lambda p: jnp.sum(gru_apply(p, x, cfg)[0] ** 2))(p)
    leaves = jax.tree.leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in leaves)
    assert any(float(jnp.max(jnp.abs(t))) > 0 for t in leaves)
