"""KV-cache serving engines: fixed-batch generate + continuous batching.

``ServeEngine`` holds jitted prefill/decode closures for one ModelConfig;
``generate`` runs greedy or temperature sampling for a batch of prompts.
``serve_step`` (module-level) is the function the decode-shape dry-run
cells lower: one new token against a seq_len KV cache.

``ContinuousBatchingEngine`` is the production path: a pool of
``Request``s is admitted/evicted per decode tick into a fixed number of
compiled batch slots, so ONE compiled tick serves a churning pool
(``analysis/recompile.assert_compiles`` proves single-compile across the
churn).  Per-request state rides traced per-row operands — ``cache_index``
(each slot decodes at its own position into the ring/linear KV cache),
temperature/top-k/top-p, and a per-request PRNG key folded with the
per-request step counter, so token *i* of a request is sampled
identically whether it shares the batch or runs alone.  Prefill is split
from the decode tick: arrivals are bucketed to power-of-two lengths,
prefilled batched per bucket in one chunked-attention forward
(``causal_lm.prefill(length=...)``), and the resulting cache rows are
scattered into free slots with a traced-slot insert.  See
docs/serving.md.

Decode hot loop: sampling is FUSED into the jitted decode step (one
compiled call per generated token — no host-side argmax/categorical
between steps), the per-step PRNG key is derived inside jit via
``fold_in``, and the loop issues exactly ``max_new_tokens - 1`` decode
calls after prefill (the old loop ran one extra decode whose logits were
discarded).  ``temperature > 0`` without a key is an error, not a silent
greedy fallback.

Non-finite robustness: a NaN/inf logit row (overflowed checkpoint,
corrupted KV cache) would send NaN through softmax and make
``jax.random.categorical`` return garbage — possibly out-of-range token
ids that crash downstream detokenizers.  ``_sample`` therefore guards
per row: any row with a non-finite logit degrades to a deterministic
in-range token (argmax over zeroed logits = token 0) instead of
propagating the NaN, and ``generate(..., return_flags=True)`` reports
which requests ever hit the guard so callers can flag/retry them.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import causal_lm as LM
from repro.models import transformer as T

__all__ = ["ServeEngine", "serve_step", "Request",
           "ContinuousBatchingEngine"]


def serve_step(params: dict, cfg: T.ModelConfig, tokens: jax.Array,
               cache, cache_index: jax.Array):
    """One decode step for the whole batch: (B,) int32 -> (logits, cache).
    This is the unit the decode dry-run cells lower + compile."""
    return LM.decode_step(params, cfg, tokens, cache, cache_index)


@obs.scoped("sample")
def _sample(logits: jax.Array, key: jax.Array, temperature: jax.Array,
            greedy: bool) -> Tuple[jax.Array, jax.Array]:
    """Traced sampling head; returns ``(tokens, bad)`` where ``bad`` is a
    per-row bool flagging rows whose logits were non-finite (those rows
    take a deterministic in-range fallback token instead of sampling from
    NaN).  ``greedy`` is static (two compiled variants); ``temperature``
    is traced so sweeping it never recompiles."""
    bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
    # zero the whole row when any entry is non-finite: argmax/categorical
    # over an all-zero row is token 0 — deterministic and always in-range
    safe = jnp.where(bad[..., None], jnp.zeros_like(logits), logits)
    if greedy:
        tok = jnp.argmax(safe, axis=-1).astype(jnp.int32)
    else:
        tok = jax.random.categorical(
            key, safe / temperature, axis=-1).astype(jnp.int32)
        tok = jnp.where(bad, jnp.zeros_like(tok), tok)
    return tok, bad


@dataclasses.dataclass
class ServeEngine:
    cfg: T.ModelConfig
    params: dict
    max_len: int
    cache_dtype: object = jnp.bfloat16

    def __post_init__(self):
        def step(params, tok, cache, prompt_len, key, step_idx,
                 temperature, greedy):
            logits, cache = serve_step(params, self.cfg, tok, cache,
                                       prompt_len + step_idx)
            k = jax.random.fold_in(key, step_idx + 1)
            tok, bad = _sample(logits, k, temperature, greedy)
            return tok, bad, cache

        # decode + sample in ONE compiled call per token
        self._step = jax.jit(step, static_argnames=("greedy",))
        self._sample_first = jax.jit(
            lambda logits, key, temperature, greedy:
                _sample(logits, jax.random.fold_in(key, 0), temperature,
                        greedy),
            static_argnames=("greedy",))

    def generate(self, prompts: jax.Array, *, max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None,
                 return_flags: bool = False,
                 ) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
        """prompts: (B, T_prompt) int32 -> (B, max_new_tokens).

        With ``return_flags=True`` returns ``(tokens, flags)`` where
        ``flags`` is a (B,) bool marking requests that hit the non-finite
        logits guard at ANY decode step (their tokens past that point are
        fallback output and the request should be flagged or retried)."""
        greedy = temperature <= 0.0
        if not greedy and key is None:
            raise ValueError("temperature > 0 requires a PRNG key")
        if max_new_tokens <= 0:
            empty = jnp.zeros((prompts.shape[0], 0), jnp.int32)
            if return_flags:
                return empty, jnp.zeros((prompts.shape[0],), bool)
            return empty
        if key is None:
            key = jax.random.PRNGKey(0)  # unused: greedy takes no samples
        logits, cache = LM.prefill(self.params, self.cfg,
                                   max_len=self.max_len, tokens=prompts,
                                   cache_dtype=self.cache_dtype)
        idx = jnp.asarray(prompts.shape[1], jnp.int32)
        temp = jnp.asarray(temperature, jnp.float32)
        tok, flags = self._sample_first(logits, key, temp, greedy=greedy)
        out = [tok]
        # the token sampled from step t's logits is decoded at step t+1;
        # the LAST sampled token is returned without a trailing decode
        for t in range(max_new_tokens - 1):
            tok, bad, cache = self._step(self.params, tok, cache, idx,
                                         key, jnp.asarray(t, jnp.int32),
                                         temp, greedy=greedy)
            flags = flags | bad
            out.append(tok)
        tokens = jnp.stack(out, axis=1)
        if return_flags:
            return tokens, flags
        return tokens


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One serving request for ``ContinuousBatchingEngine``.

    ``temperature <= 0`` is greedy; ``top_k <= 0`` (or >= vocab) and
    ``top_p`` outside (0, 1) disable those filters bit-exactly.  ``rid``
    pins the per-request PRNG stream (``fold_in(base_key, rid)``) and the
    result key; auto-assigned monotonically when None."""
    prompt: object
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    rid: Optional[int] = None


@obs.scoped("sample")
def _sample_rows(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                 top_k: jax.Array, top_p: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Per-row sampling head: each row has its own PRNG key, temperature,
    top-k, and top-p, all traced — one compiled variant serves every mix.

    Per-row math only (no cross-row reductions), so a row's token is
    bitwise-identical whether it shares the batch or samples alone.
    Greedy rows (``temperature <= 0``) take argmax; non-finite rows
    degrade to token 0 and are flagged in the returned ``bad`` mask, as in
    ``_sample``."""
    V = logits.shape[-1]
    bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
    safe = jnp.where(bad[..., None], jnp.zeros_like(logits), logits)
    greedy_tok = jnp.argmax(safe, axis=-1).astype(jnp.int32)

    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = safe.astype(jnp.float32) / t
    # top-k: kth-largest logit is the keep threshold (traced k per row)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(desc, jnp.clip(top_k - 1, 0, V - 1)[:, None],
                              axis=-1)
    apply_k = ((top_k > 0) & (top_k < V))[:, None]
    scaled = jnp.where(apply_k & (scaled < kth), -jnp.inf, scaled)
    # top-p (nucleus) over the k-filtered distribution: keep the smallest
    # prefix of the sorted probs whose mass reaches p — i.e. drop a token
    # only if the mass strictly above it already covers p
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    mass_above = jnp.cumsum(probs, axis=-1) - probs
    kept = mass_above < top_p[:, None]
    thr = jnp.min(jnp.where(kept, desc, jnp.inf), axis=-1, keepdims=True)
    apply_p = ((top_p > 0.0) & (top_p < 1.0))[:, None]
    scaled = jnp.where(apply_p & (scaled < thr), -jnp.inf, scaled)

    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    tok = jnp.where((temperature <= 0.0) | bad,
                    greedy_tok, sampled.astype(jnp.int32))
    return tok, bad


class ContinuousBatchingEngine:
    """Continuous-batching serve loop over ``slots`` compiled batch rows.

    The decode tick is jitted ONCE: every per-request quantity it touches
    (last token, cache position, sampling params, PRNG key, step counter,
    active mask) is a traced per-row operand, so admitting/evicting
    requests never retraces.  Prefill compiles per (bucket, group-size)
    pair — buckets are power-of-two so a handful of shapes serve any
    prompt-length mix.  Inactive slots still decode (their row is masked
    and their cache row is fully replaced at the next admit), which keeps
    the tick shape fixed.

    Only attention-mixer stacks are supported: chunked prefill and the
    per-row-``cache_index`` decode both need KV caches (SSM caches are
    strictly sequential single-token)."""

    def __init__(self, cfg: T.ModelConfig, params: dict, *, slots: int,
                 max_len: int, cache_dtype=jnp.bfloat16,
                 base_key: Optional[jax.Array] = None):
        if any(s.mixer != "attn" for s in cfg.layers):
            raise ValueError(
                "ContinuousBatchingEngine needs an attention-only stack; "
                f"{cfg.name} has SSM mixers (use ServeEngine)")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.base_key = (jax.random.PRNGKey(0) if base_key is None
                         else base_key)
        self._next_rid = 0
        # cache leaves are (B, S, H, D) unrolled / (G, B, S, H, D) scanned
        self._batch_axis = 1 if cfg.scanned else 0

        def tick(params, tok, cache, ci, active, keys, steps,
                 temp, top_k, top_p):
            logits, cache = LM.decode_step(params, cfg, tok, cache, ci)
            ks = jax.vmap(jax.random.fold_in)(keys, steps)
            new_tok, bad = _sample_rows(logits, ks, temp, top_k, top_p)
            new_tok = jnp.where(active, new_tok, tok)
            ci = jnp.where(active, ci + 1, ci)
            steps = jnp.where(active, steps + 1, steps)
            return new_tok, bad, cache, ci, steps

        def prefill(params, tokens, length):
            return LM.prefill(params, cfg, max_len=max_len, tokens=tokens,
                              cache_dtype=cache_dtype, length=length)

        def sample_first(logits, keys, temp, top_k, top_p):
            ks = jax.vmap(jax.random.fold_in)(
                keys, jnp.zeros((keys.shape[0],), jnp.int32))
            return _sample_rows(logits, ks, temp, top_k, top_p)

        def insert(cache, pcache, row, slot):
            ax = self._batch_axis

            def one(c, p):
                r = jax.lax.dynamic_index_in_dim(p, row, axis=ax,
                                                 keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    c, r.astype(c.dtype), slot, axis=ax)
            return jax.tree.map(one, cache, pcache)

        self._tick = jax.jit(tick)          # compiles ONCE for all churn
        self._prefill = jax.jit(prefill)    # per (bucket, group) shape
        self._sample_first = jax.jit(sample_first)
        self._insert = jax.jit(insert)      # traced row + slot

    # ---- host-side pool state -------------------------------------------

    def _reset(self):
        S = self.slots
        self._cache = T.init_cache(S, self.max_len, self.cfg,
                                   self.cache_dtype)
        self._tok = jnp.zeros((S,), jnp.int32)
        self._ci = jnp.zeros((S,), jnp.int32)
        self._active = jnp.zeros((S,), bool)
        self._keys = jnp.stack([jax.random.PRNGKey(0)] * S)
        self._steps = jnp.zeros((S,), jnp.int32)
        self._temp = jnp.zeros((S,), jnp.float32)
        self._topk = jnp.zeros((S,), jnp.int32)
        self._topp = jnp.ones((S,), jnp.float32)
        self._slot_req: list = [None] * S

    def _bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _admit(self, batch, tick_idx, results):
        """Bucket-batched prefill of ``batch`` [(slot, Request)], then
        scatter each prefilled row + its per-request state into its slot.
        Stamps each request's ``admit_s`` and ``first_token_s``."""
        t_admit = time.time()
        with obs.span("serve.admit", tick=tick_idx,
                      rids=[r.rid for _, r in batch]):
            groups: dict = {}
            for slot, req in batch:
                results[req.rid]["admit_s"] = t_admit
                prompt = jnp.asarray(req.prompt, jnp.int32).reshape(-1)
                groups.setdefault(self._bucket(prompt.shape[0]),
                                  []).append((slot, req, prompt))
            for bucket, members in sorted(groups.items()):
                self._admit_bucket(bucket, members, tick_idx, results)

    def _admit_bucket(self, bucket, members, tick_idx, results):
        with obs.span("serve.pack", bucket=bucket):
            toks = jnp.stack(
                [jnp.pad(p, (0, bucket - p.shape[0])) for _, _, p in members])
            lens = jnp.asarray([p.shape[0] for _, _, p in members], jnp.int32)
            keys = jnp.stack([jax.random.fold_in(self.base_key, r.rid)
                              for _, r, _ in members])
            temp = jnp.asarray([r.temperature for _, r, _ in members],
                               jnp.float32)
            topk = jnp.asarray([r.top_k for _, r, _ in members], jnp.int32)
            topp = jnp.asarray([r.top_p for _, r, _ in members], jnp.float32)
        with obs.span("serve.prefill", bucket=bucket, group=len(members)):
            logits, pcache = self._prefill(self.params, toks, lens)
        with obs.span("serve.first_token"):
            first, bad = self._sample_first(logits, keys, temp, topk, topp)
            first, bad = jax.device_get((first, bad))
            t_first = time.time()
        with obs.span("serve.insert"):
            for g, (slot, req, prompt) in enumerate(members):
                self._cache = self._insert(self._cache, pcache,
                                           jnp.asarray(g, jnp.int32),
                                           jnp.asarray(slot, jnp.int32))
                self._tok = self._tok.at[slot].set(int(first[g]))
                self._ci = self._ci.at[slot].set(prompt.shape[0])
                self._keys = self._keys.at[slot].set(keys[g])
                self._steps = self._steps.at[slot].set(1)
                self._temp = self._temp.at[slot].set(req.temperature)
                self._topk = self._topk.at[slot].set(req.top_k)
                self._topp = self._topp.at[slot].set(req.top_p)
                self._active = self._active.at[slot].set(True)
                res = results[req.rid]
                res["tokens"].append(int(first[g]))
                res["flagged"] |= bool(bad[g])
                res["admitted_tick"] = tick_idx
                res["first_token_s"] = t_first
                self._slot_req[slot] = req

    def serve(self, requests, *, arrival_ticks=None):
        """Serve ``requests`` (list of :class:`Request`) to completion.

        ``arrival_ticks[i]`` (default 0) is the decode tick at which
        request *i* becomes admissible — the knob load generators use to
        model offered load.  Returns ``(results, stats)``: ``results``
        maps rid -> {tokens, flagged, admitted_tick, finished_tick,
        arrival_s, admit_s, first_token_s}, the last three host-clock
        stamps (``time.time()``): the start of the request's arrival tick
        (or of the first tick run at or after it), the start of its
        admission, and its first token's arrival on the host;
        ``stats`` has ``ticks``, ``tokens`` (decoded total incl. prefill
        samples), and ``occupied_slot_ticks`` for occupancy/latency
        accounting.  Each loop iteration's host work lies in the
        ``obs.SPANS`` spans (docs/serving.md, Observability)."""
        for r in requests:
            if r.rid is None:
                r.rid = self._next_rid
                self._next_rid += 1
            if r.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            n = jnp.asarray(r.prompt).reshape(-1).shape[0]
            if n + r.max_new_tokens > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({n}) + max_new_tokens "
                    f"({r.max_new_tokens}) exceeds max_len={self.max_len}")
        arrival_ticks = list(arrival_ticks or [0] * len(requests))
        pending = sorted(zip(arrival_ticks, range(len(requests))))
        unstamped = list(pending)           # arrivals without arrival_s
        results = {r.rid: {"tokens": [], "flagged": False,
                           "admitted_tick": None, "finished_tick": None,
                           "arrival_s": None, "admit_s": None,
                           "first_token_s": None}
                   for r in requests}
        self._reset()
        stats = {"ticks": 0, "tokens": 0, "occupied_slot_ticks": 0}
        for tick_idx in itertools.count():
            with obs.span("serve.schedule"):
                if not pending and all(r is None for r in self._slot_req):
                    break
                now = time.time()
                while unstamped and unstamped[0][0] <= tick_idx:
                    results[requests[unstamped.pop(0)[1]].rid][
                        "arrival_s"] = now
                # admit arrivals into free slots
                free = [s for s in range(self.slots)
                        if self._slot_req[s] is None]
                batch = []
                while pending and free and pending[0][0] <= tick_idx:
                    _, i = pending.pop(0)
                    batch.append((free.pop(0), requests[i]))
            if batch:
                self._admit(batch, tick_idx, results)
            with obs.span("serve.bookkeep"):
                # a max_new_tokens == 1 admit finishes without decoding
                for s, req in batch:
                    if len(results[req.rid]["tokens"]) >= req.max_new_tokens:
                        results[req.rid]["finished_tick"] = tick_idx
                        self._active = self._active.at[s].set(False)
                        self._slot_req[s] = None
                stats["tokens"] += len(batch)
                stats["ticks"] += 1
                n_active = sum(r is not None for r in self._slot_req)
            if not n_active:
                continue
            with obs.span("serve.tick", tick=tick_idx):
                self._tok, bad, self._cache, self._ci, self._steps = \
                    self._tick(self.params, self._tok, self._cache, self._ci,
                               self._active, self._keys, self._steps,
                               self._temp, self._topk, self._topp)
            with obs.span("serve.fetch"):
                tok_h, bad_h = jax.device_get((self._tok, bad))
            with obs.span("serve.bookkeep"):
                for s in range(self.slots):
                    req = self._slot_req[s]
                    if req is not None:
                        res = results[req.rid]
                        res["tokens"].append(int(tok_h[s]))
                        res["flagged"] |= bool(bad_h[s])
                        if len(res["tokens"]) >= req.max_new_tokens:
                            res["finished_tick"] = tick_idx
                            self._active = self._active.at[s].set(False)
                            self._slot_req[s] = None
                stats["tokens"] += n_active
                stats["occupied_slot_ticks"] += n_active
        return results, stats
