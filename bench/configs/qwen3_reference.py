"""Plain float32 reference of a Qwen3 decoder whose projections are
Stagewise Pairwise Mixers (SPM), with its loss, for one row at a time.

Written from the published descriptions, not from the program: Qwen3
(``config.json`` of Qwen/Qwen3-*: pre-norm RMSNorm decoder, GQA with
per-head RMS qk-norm, rotate-half RoPE, SwiGLU FFN, tied or untied LM
head) and SPM (arXiv 2512.23905: ``y = D_out B_L ... B_1 D_in x`` with
2x2 mixes on the pairs ``(i, i + s)`` of each block of ``2 s`` lanes,
butterfly strides, ``L = min(ceil(log2 n), 12)`` stages; a map
``d_in -> d_out`` runs on ``n = even(max(d_in, d_out))`` lanes, zero
filled on input and cut to ``d_out`` on output).  The parameter tree is
the one the benchmark's weight generator fills, named as the program
stores it: ``embed.table``/``embed.out``, ``final_norm.scale`` and the
layers stacked on a leading axis under ``layers.l0``.

Every matrix product runs at ``Precision.HIGHEST``.  ``precision`` rounds
each activation where a lower-precision implementation would store it
(``"f32"``: no rounding; ``"bf16"``; ``"fp8"``: e4m3, 3 mantissa bits) —
the control that the comparison must reject.  Memory stays bounded by
rematerialising each layer, each SPM map and each attention block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
EPS = 1e-6
Q_BLOCK = 1024
HEAD_BLOCK = 1024
ROUNDING = {"f32": None, "bf16": (8, 7), "fp8": (4, 3)}


def rounder(precision: str):
    bits = ROUNDING[precision]
    if bits is None:
        return lambda x: x
    return lambda x: lax.reduce_precision(x, exponent_bits=bits[0],
                                          mantissa_bits=bits[1])


def butterfly_strides(n: int, n_stages: int):
    """Power-of-two strides ascending, then for ``n = 2^k m`` (m odd) the
    super-strides ``m 2^j`` largest first, cycled to ``n_stages``."""
    base, s = [], 1
    while n % (2 * s) == 0:
        base.append(s)
        s *= 2
    k, m = len(base), n >> len(base)
    cross = [m << j for j in range(k - 1, -1, -1)
             if m > 1 and n % (2 * (m << j)) == 0]
    cycle = base + cross
    return tuple(cycle[i % len(cycle)] for i in range(n_stages))


def spm_stage(z, coeffs, s):
    n = z.shape[-1]
    g = n // (2 * s)
    zr = z.reshape(z.shape[:-1] + (g, 2, s))
    x0, x1 = zr[..., 0, :], zr[..., 1, :]
    a, b, c, d = (coeffs[:, i].reshape(g, s) for i in range(4))
    return jnp.stack([a * x0 + b * x1, c * x0 + d * x1],
                     axis=-2).reshape(z.shape)


def rms(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * scale


def rope(x, pos, theta):
    """Rotate-half RoPE; x (T, heads, dh), pos (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class Qwen3SPM:
    """The reference for one configuration (``shape``: the published
    dict of the configuration file) at one ``precision``."""

    def __init__(self, shape: dict, precision: str = "f32"):
        self.s = shape
        self.rnd = rounder(precision)
        self.H = shape["num_attention_heads"]
        self.Hkv = shape["num_key_value_heads"]
        self.dh = shape["head_dim"]
        self.d = shape["hidden_size"]
        self.f = shape["intermediate_size"]
        self.tied = shape["tie_word_embeddings"]
        self.theta = float(shape["rope_theta"])

    # ---- pieces -------------------------------------------------------
    def spm(self, p, x, d_out):
        @jax.checkpoint
        def run(p, x):
            n = p["d_in"].shape[-1]
            L = p["mix"].shape[-3]
            if x.shape[-1] < n:
                x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                            + [(0, n - x.shape[-1])])
            z = x * p["d_in"]
            for ell, s in enumerate(butterfly_strides(n, L)):
                z = spm_stage(z, p["mix"][ell], s)
            return self.rnd((z * p["d_out"])[..., :d_out])
        return run(p, x)

    def attend(self, q, k, v):
        """Causal GQA over one row: q (T, H, dh), k/v (T, Hkv, dh)."""
        T = q.shape[0]
        G = self.H // self.Hkv
        qg = q.reshape(T, self.Hkv, G, self.dh) * self.dh ** -0.5

        @functools.partial(jax.checkpoint, static_argnums=(3,))
        def block(qb, k, v, off):
            s = jnp.einsum("thgd,shd->hgts", qb, k, precision=HI)
            keep = (jnp.arange(T)[None, :]
                    <= off + jnp.arange(qb.shape[0])[:, None])
            s = jnp.where(keep, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hgts,shd->thgd", p, v, precision=HI)

        outs = [block(qg[o:o + Q_BLOCK], k, v, o)
                for o in range(0, T, Q_BLOCK)]
        return jnp.concatenate(outs, axis=0).reshape(T, self.H * self.dh)

    def layer(self, lp, h, pos):
        rnd, H, Hkv, dh = self.rnd, self.H, self.Hkv, self.dh
        at = lp["mixer"]
        x = rnd(rms(h, lp["norm1"]["scale"]))
        q = self.spm(at["q"], x, H * dh).reshape(-1, H, dh)
        k = self.spm(at["k"], x, Hkv * dh).reshape(-1, Hkv, dh)
        v = self.spm(at["v"], x, Hkv * dh).reshape(-1, Hkv, dh)
        q = rnd(rope(rnd(rms(q, at["q_norm"])), pos, self.theta))
        k = rnd(rope(rnd(rms(k, at["k_norm"])), pos, self.theta))
        a = rnd(self.attend(q, k, v))
        h = rnd(h + self.spm(at["o"], a, self.d))
        ff = lp["mlp"]
        x = rnd(rms(h, lp["norm2"]["scale"]))
        g = self.spm(ff["gate"], x, self.f)
        u = self.spm(ff["up"], x, self.f)
        m = rnd(jax.nn.silu(g) * u)
        return rnd(h + self.spm(ff["down"], m, self.d))

    def hidden(self, params, tokens):
        """Final-normed hidden states of one row of token ids (T,)."""
        pos = jnp.arange(tokens.shape[0])
        h = self.rnd(params["embed"]["table"][tokens])

        @jax.checkpoint
        def body(h, lp):
            return self.layer(lp, h, pos), None

        h, _ = lax.scan(body, h, params["layers"]["l0"])
        return self.rnd(rms(h, params["final_norm"]["scale"]))

    def head(self, params, h):
        if self.tied:
            return jnp.einsum("td,vd->tv", h,
                              self.rnd(params["embed"]["table"]),
                              precision=HI)
        return jnp.einsum("td,dv->tv", h, self.rnd(params["embed"]["out"]),
                          precision=HI)

    # ---- entry points -------------------------------------------------
    def row_nll_sum(self, params, tokens, labels):
        """Summed next-token cross-entropy of one row."""
        h = self.hidden(params, tokens)

        @jax.checkpoint
        def chunk(h, y):
            lg = self.head(params, h)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None],
                                                     axis=-1)[:, 0])

        T = tokens.shape[0]
        return sum(chunk(h[o:o + HEAD_BLOCK], labels[o:o + HEAD_BLOCK])
                   for o in range(0, T, HEAD_BLOCK))

    def logits_at(self, params, tokens, positions):
        """Logits of one row (T,) at ``positions`` (K,)."""
        h = self.hidden(params, tokens)
        return self.head(params, h[positions])


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "tie_word_embeddings",
        "rope_theta")


def _key(shape: dict):
    return tuple((k, shape[k]) for k in KEYS)


@functools.lru_cache(maxsize=None)
def _row_grad(shape_items, precision):
    model = Qwen3SPM(dict(shape_items), precision)
    return jax.jit(jax.value_and_grad(model.row_nll_sum))


def loss_and_grads(shape: dict, precision: str, params, tokens, labels):
    """Mean next-token loss over a batch (B, T) and its gradient, summed
    row by row."""
    f = _row_grad(_key(shape), precision)
    B, T = tokens.shape
    total, grads = 0.0, None
    for r in range(B):
        v, g = f(params, tokens[r], labels[r])
        total = total + v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = B * T
    return total / n, jax.tree.map(lambda x: x / n, grads)


@functools.lru_cache(maxsize=None)
def _served(shape_items, precision):
    model = Qwen3SPM(dict(shape_items), precision)
    return jax.jit(model.logits_at)


def logits_at(shape: dict, precision: str, params, tokens, positions):
    """Logits of one row at the given positions (one compiled program per
    row length and position count)."""
    return _served(_key(shape), precision)(
        params, tokens, positions)
