"""The causal flash-attention kernel (``kernels/attention.py``) against the
chunked online-softmax path, and the dispatch between the two
(``layers.attention.fresh_causal_attention``).

Off-TPU the kernel runs in interpret mode.  Kernel and reference take the
same bf16 q, k and v.  The kernel rounds its scaled q, its output and its
backward's probability and score-gradient tiles to bf16; the chunked path
computes in f32 from the same inputs and rounds only its cotangents.  So
a relative L2 of twice the bf16 unit roundoff (2**-8) bounds a sound
kernel, and a wrong mask, group or scale reads far above it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.kernels import attention as flash
from repro.kernels import ops
from repro.layers import attention as A
from repro.parallel.ctx import activation_sharding

BF16_U = 2.0 ** -8
REL_LIMIT = 2 * BF16_U

SHAPES = [(1, 256, 2, 1), (2, 512, 4, 2), (1, 256, 8, 1)]   # B, T, H, Hkv


def _qkv(B, T, H, Hkv, dh=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H, dh), jnp.bfloat16),
            jax.random.normal(ks[1], (B, T, Hkv, dh), jnp.bfloat16),
            jax.random.normal(ks[2], (B, T, Hkv, dh), jnp.bfloat16),
            jax.random.normal(ks[3], (B, T, H, dh), jnp.bfloat16))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _chunked(q, k, v):
    return A.chunked_causal_attention(q, k, v, q_chunk=128, k_chunk=128)


@pytest.fixture(params=["blocks_of_T", "blocks_128"])
def blocks(request, monkeypatch):
    """The kernel's own block sizes at T, and 128-wide blocks, which split
    every test length into several query and key blocks so that blocks
    wholly above the diagonal are skipped."""
    if request.param == "blocks_128":
        small = flash.block_sizes(128)
        monkeypatch.setattr(flash, "block_sizes", lambda T: small)
    return request.param


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_forward_matches_chunked(shape, blocks):
    q, k, v, _ = _qkv(*shape)
    out = jax.jit(flash.causal_attention)(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert _rel(out, _chunked(q, k, v)) < REL_LIMIT


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_vjp_matches_chunked(shape, blocks):
    q, k, v, ct = _qkv(*shape)

    def grads(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return pull(ct.astype(out.dtype))

    got = jax.jit(lambda: grads(flash.causal_attention))()
    want = grads(_chunked)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        assert _rel(g, w) < REL_LIMIT, (name, _rel(g, w))


def test_a_shifted_mask_reads_far_above_the_limit():
    """The limit catches a fault: attention that lets each query see one
    key too many is far outside it."""
    q, k, v, _ = _qkv(1, 256, 2, 1)
    out = flash.causal_attention(q, k, v)
    shifted = A.chunked_causal_attention(q, k, v, q_offset=1, q_chunk=128,
                                         k_chunk=128)
    assert _rel(out, shifted) > 10 * REL_LIMIT


def test_kernel_is_built_once_per_shape():
    q, k, v, _ = _qkv(1, 384, 2, 1)
    flash._mqa_kernel.cache_clear()
    jax.jit(flash.causal_attention)(q, k, v)
    jax.jit(lambda a, b, c: 2 * flash.causal_attention(a, b, c))(q, k, v)
    info = flash._mqa_kernel.cache_info()
    assert (info.misses, info.hits) == (1, 1)


@pytest.mark.parametrize("T, block", [(128, 128), (384, 384), (640, 640),
                                      (1536, 768), (4096, 1024)])
def test_blocks_are_the_largest_divisor_up_to_1024(T, block):
    bs = flash.block_sizes(T)
    assert (bs.block_q, bs.block_kv, bs.block_q_dkv, bs.block_kv_dkv) \
        == (block,) * 4
    assert bs.use_fused_bwd_kernel


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """A TPU backend as the dispatch sees it, with the kernel still in
    interpret mode, and a record of each time the kernel is taken."""
    calls = []
    real = flash.causal_attention

    def spy(q, k, v):
        calls.append(q.shape)
        return real(q, k, v)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "default_interpret", lambda: True)
    monkeypatch.setattr(flash, "causal_attention", spy)
    return calls


def test_dispatch_takes_the_kernel_where_it_applies(on_tpu):
    q, k, v, _ = _qkv(1, 256, 4, 2)
    out = A.fresh_causal_attention(q, k, v, q_chunk=128, k_chunk=128)
    assert on_tpu == [q.shape]
    assert _rel(out, _chunked(q, k, v)) < REL_LIMIT


def _fallbacks():
    full = _qkv(1, 256, 4, 2)
    odd = _qkv(1, 200, 4, 2)
    narrow = _qkv(1, 256, 4, 2, dh=64)
    return {
        "window": (full, dict(window=64)),
        "q_offset": (full, dict(q_offset=3)),
        "length_not_a_multiple_of_128": (odd, {}),
        "head_dim_64": (narrow, {}),
        "sharded": (full, {}),
    }


@pytest.mark.parametrize("case", list(_fallbacks()))
def test_dispatch_falls_back_to_the_chunked_path(case, on_tpu):
    (q, k, v, _), kw = _fallbacks()[case]
    kw = dict(kw, q_chunk=128, k_chunk=128)
    if case == "sharded":
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
        with activation_sharding(mesh):
            out = A.fresh_causal_attention(q, k, v, **kw)
    else:
        out = A.fresh_causal_attention(q, k, v, **kw)
    assert on_tpu == []
    want = A.chunked_causal_attention(q, k, v, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_dispatch_off_tpu_is_the_chunked_path():
    assert jax.default_backend() != "tpu"
    q, k, v, _ = _qkv(1, 256, 4, 2)
    out = A.fresh_causal_attention(q, k, v, q_chunk=128, k_chunk=128)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_chunked(q, k, v)))
