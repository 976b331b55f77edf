"""Plain float32 reference of Qwen3-MoE (Qwen/Qwen3-30B-A3B) whose
projections and expert maps are Stagewise Pairwise Mixers (SPM), with its
training loss, for one row at a time.

Written from the published description (``config.json`` of
Qwen/Qwen3-30B-A3B and HF's ``Qwen3MoeSparseMoeBlock`` /
``load_balancing_loss_func``), not from the program.  The attention, the
norms, RoPE, the LM head and the SPM maps are ``Qwen3SPM``'s
(``qwen3_reference.py``, loaded, not edited).  Every layer's FFN is the
sparse block instead:

* router: ``logits = x W_r`` (``d -> E``, no bias), ``p = softmax(logits)``
  over all ``num_experts`` in f32; each token takes the
  ``num_experts_per_tok`` largest ``p``, renormalised over them when
  ``norm_topk_prob`` holds;
* each chosen expert's SwiGLU ``down(silu(gate(x)) * up(x))`` (widths
  ``d -> moe_intermediate_size -> d``, every map an SPM) on exactly the
  tokens routed to it, summed with the gates;
* loss: mean next-token cross-entropy plus ``router_aux_loss_coef`` times
  HF's load-balancing loss, ``E * sum_e f_e * P_e`` with ``f_e`` the share
  of (token, layer) rows that chose expert ``e`` and ``P_e`` its mean
  router probability, the means over every layer's tokens of the batch
  (HF concatenates the layers' router logits).

Departures, each noted: the published ``output_router_logits`` is false
(the inference default) — training adds the load-balancing loss anyway,
as the configuration file's ``assumed`` says; expert maps are SPM, not
dense (the system's technique); a routed row's expert parameters are
picked by a one-hot product at ``Precision.HIGHEST``, which is exact, so
that the gradients of all experts come from one product per stage.  The
rows of the batch go through ``lax.map`` with each row rematerialised,
so memory holds one row at a time; within a row the routed rows and the
attention's query blocks go through ``lax.map`` too (the same sums in
blocks), so that an 8k row's f32 loss and gradients fit beside the
optimizer state on one chip.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

import spec

_base = spec.load_module(os.path.join(os.path.dirname(__file__),
                                      "qwen3_reference.py"),
                         "bench_qwen3_reference_for_moe")

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512         # routed rows per rematerialised expert block
Q_BLOCK = 512           # query rows per attention block


class Qwen3MoESPM(_base.Qwen3SPM):
    """``Qwen3SPM`` with every FFN replaced by the sparse block."""

    def __init__(self, shape: dict, precision: str = "f32"):
        super().__init__(shape, precision)
        self.E = shape["num_experts"]
        self.k = shape["num_experts_per_tok"]
        self.fe = shape["moe_intermediate_size"]
        self.norm_topk = shape["norm_topk_prob"]
        self.aux_coef = float(shape["router_aux_loss_coef"])

    def attend(self, q, k, v):
        """``Qwen3SPM.attend`` with its query blocks in a ``lax.map``, so
        that one block's scores are live at a time."""
        T = q.shape[0]
        blk = min(Q_BLOCK, T)
        if T % blk:
            return super().attend(q, k, v)
        G = self.H // self.Hkv
        qg = q.reshape(T // blk, blk, self.Hkv, G, self.dh) * self.dh ** -0.5

        @jax.checkpoint
        def block(qo):
            qb, off = qo
            s = jnp.einsum("thgd,shd->hgts", qb, k, precision=HI)
            keep = (jnp.arange(T)[None, :]
                    <= off + jnp.arange(blk)[:, None])
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return jnp.einsum("hgts,shd->thgd", p, v, precision=HI)

        out = lax.map(block, (qg, jnp.arange(0, T, blk)))
        return out.reshape(T, self.H * self.dh)

    # ---- the sparse block ---------------------------------------------
    def spm_rows(self, p, onehot, x, d_out):
        """Each row of ``x`` through the SPM map of its own expert
        (``onehot`` (R, E) picks it from the stacked ``p``)."""
        @jax.checkpoint
        def run(p, onehot, x):
            n = p["d_in"].shape[-1]
            pick = lambda a: jnp.dot(onehot, a.reshape(self.E, -1),
                                     precision=HI)
            if x.shape[-1] < n:
                x = jnp.pad(x, ((0, 0), (0, n - x.shape[-1])))
            z = x * pick(p["d_in"])
            L = p["mix"].shape[-3]
            for ell, s in enumerate(_base.butterfly_strides(n, L)):
                cf = pick(p["mix"][:, ell]).reshape(-1, n // 2, 4)
                z = stage_rows(z, cf, s)
            return self.rnd((z * pick(p["d_out"]))[:, :d_out])
        return run(p, onehot, x)

    def expert_ffn(self, ex, onehot, x):
        g = self.spm_rows(ex["gate"], onehot, x, self.fe)
        u = self.spm_rows(ex["up"], onehot, x, self.fe)
        m = self.rnd(jax.nn.silu(g) * u)
        return self.spm_rows(ex["down"], onehot, m, self.d)

    def sparse_block(self, mp, x):
        """x (T, d) -> (y (T, d), chosen-expert counts (E,), summed router
        probabilities (E,))."""
        T = x.shape[0]
        probs = jax.nn.softmax(jnp.dot(x, mp["router"], precision=HI),
                               axis=-1)
        topv, topi = lax.top_k(probs, self.k)
        gates = topv / jnp.sum(topv, -1, keepdims=True) if self.norm_topk \
            else topv
        # every (token, slot) row, token-major, in blocks of ROW_BLOCK
        R = T * self.k
        blk = min(ROW_BLOCK, R)
        pad = -R % blk
        rows_x = jnp.pad(jnp.repeat(x, self.k, axis=0), ((0, pad), (0, 0)))
        onehot = jax.nn.one_hot(jnp.pad(topi.reshape(-1), (0, pad),
                                        constant_values=-1),
                                self.E, dtype=x.dtype)
        y = lax.map(lambda a: self.expert_ffn(mp["experts"], *a),
                    (onehot.reshape(-1, blk, self.E),
                     rows_x.reshape(-1, blk, self.d)))
        y = y.reshape(-1, self.d)[:R].reshape(T, self.k, self.d)
        y = self.rnd(jnp.sum(gates[..., None] * y, axis=1))
        return y, jnp.sum(onehot, axis=0), jnp.sum(probs, axis=0)

    def layer_stats(self, lp, h, pos):
        """One decoder layer: ``Qwen3SPM.layer``'s attention half, then
        the sparse block; also this layer's routing sums."""
        rnd, H, Hkv, dh = self.rnd, self.H, self.Hkv, self.dh
        at = lp["mixer"]
        x = rnd(_base.rms(h, lp["norm1"]["scale"]))
        q = self.spm(at["q"], x, H * dh).reshape(-1, H, dh)
        k = self.spm(at["k"], x, Hkv * dh).reshape(-1, Hkv, dh)
        v = self.spm(at["v"], x, Hkv * dh).reshape(-1, Hkv, dh)
        q = rnd(_base.rope(rnd(_base.rms(q, at["q_norm"])), pos, self.theta))
        k = rnd(_base.rope(rnd(_base.rms(k, at["k_norm"])), pos, self.theta))
        a = rnd(self.attend(q, k, v))
        h = rnd(h + self.spm(at["o"], a, self.d))
        x = rnd(_base.rms(h, lp["norm2"]["scale"]))
        y, counts, psum = self.sparse_block(lp["mlp"], x)
        return rnd(h + y), counts, psum

    def hidden_stats(self, params, tokens):
        """Final-normed hidden states of one row (T,), with the routing
        sums over its layers (counts and probabilities, (E,) each)."""
        pos = jnp.arange(tokens.shape[0])
        h = self.rnd(params["embed"]["table"][tokens])

        @jax.checkpoint
        def body(h, lp):
            h, c, p = self.layer_stats(lp, h, pos)
            return h, (c, p)

        h, (c, p) = lax.scan(body, h, params["layers"]["l0"])
        return (self.rnd(_base.rms(h, params["final_norm"]["scale"])),
                jnp.sum(c, 0), jnp.sum(p, 0))

    def hidden(self, params, tokens):
        return self.hidden_stats(params, tokens)[0]

    # ---- entry points -------------------------------------------------
    def row_terms(self, params, tokens, labels):
        """Summed next-token cross-entropy of one row and its routing
        sums."""
        h, counts, psum = self.hidden_stats(params, tokens)

        @jax.checkpoint
        def chunk(h, y):
            lg = self.head(params, h)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None],
                                                     axis=-1)[:, 0])

        T = tokens.shape[0]
        nll = sum(chunk(h[o:o + _base.HEAD_BLOCK],
                        labels[o:o + _base.HEAD_BLOCK])
                  for o in range(0, T, _base.HEAD_BLOCK))
        return nll, counts, psum

    def batch_loss(self, params, tokens, labels):
        """Mean cross-entropy of a batch (B, T) plus the load-balancing
        loss over all its rows and layers."""
        B, T = tokens.shape
        L = params["layers"]["l0"]["mlp"]["router"].shape[0]
        nll, counts, psum = lax.map(
            jax.checkpoint(lambda tl: self.row_terms(params, *tl)),
            (tokens, labels))
        n = B * T * L
        f = jnp.sum(counts, 0) / n
        P = jnp.sum(psum, 0) / n
        return jnp.sum(nll) / (B * T) + self.aux_coef * self.E * jnp.sum(
            f * P)


def stage_rows(z, coeffs, s):
    """One butterfly stage with per-row coefficients: z (R, n), coeffs
    (R, n/2, 4) on the pairs ``(i, i + s)`` of each block of ``2 s``."""
    R, n = z.shape
    g = n // (2 * s)
    zr = z.reshape(R, g, 2, s)
    x0, x1 = zr[:, :, 0], zr[:, :, 1]
    a, b, c, d = (coeffs[..., i].reshape(R, g, s) for i in range(4))
    return jnp.stack([a * x0 + b * x1, c * x0 + d * x1],
                     axis=2).reshape(R, n)


KEYS = _base.KEYS + ("num_experts", "num_experts_per_tok",
                     "moe_intermediate_size", "norm_topk_prob",
                     "router_aux_loss_coef")


def _key(shape: dict):
    return tuple((k, shape[k]) for k in KEYS)


@functools.lru_cache(maxsize=None)
def _batch_grad(shape_items, precision):
    model = Qwen3MoESPM(dict(shape_items), precision)
    return jax.jit(jax.value_and_grad(model.batch_loss))


def loss_and_grads(shape: dict, precision: str, params, tokens, labels):
    """The training loss of a batch (B, T) and its gradient."""
    return _batch_grad(_key(shape), precision)(params, tokens, labels)


@functools.lru_cache(maxsize=None)
def _served(shape_items, precision):
    model = Qwen3MoESPM(dict(shape_items), precision)
    return jax.jit(model.logits_at)


def logits_at(shape: dict, precision: str, params, tokens, positions):
    """Logits of one row at the given positions."""
    return _served(_key(shape), precision)(params, tokens, positions)
