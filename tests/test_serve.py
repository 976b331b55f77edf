"""Continuous-batching engine acceptance (serve/engine.py).

The two contracts the tentpole rests on:

1. BITWISE churn parity — a request decodes the exact same tokens
   whether it shares the slot pool with churning neighbours (mixed
   prompt lengths, temperatures, top-k/top-p, staggered arrivals) or is
   served alone on a single-slot engine.  Per-request PRNG keys
   (``fold_in(base_key, rid)`` folded with the per-request step counter)
   and the per-row-only sampling math make this exact, not approximate.

2. SINGLE-COMPILE decode tick — after one warmup request, serving an
   arbitrary mix of requests adds ZERO executable-cache entries to the
   jitted tick (``analysis/recompile.assert_compiles``): every
   per-request quantity is a traced per-row operand.

Plus the non-finite-logits flag propagation through both engines.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.recompile import assert_compiles
from repro.configs import get_smoke
from repro.models import transformer as T
from repro.serve import ContinuousBatchingEngine, Request, ServeEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke("qwen3-1.7b")
    params = T.init_model(KEY, cfg)
    return cfg, params


def _requests(vocab):
    """A churn mix: every bucket, greedy + sampled, k/p filters on/off."""
    specs = [
        # (prompt_len, max_new, temperature, top_k, top_p)
        (8, 5, 0.0, 0, 1.0),     # greedy, exact-bucket prompt
        (5, 6, 0.8, 0, 1.0),     # plain temperature sampling
        (12, 4, 1.2, 5, 1.0),    # top-k
        (24, 6, 0.7, 0, 0.9),    # top-p
        (7, 3, 1.0, 50, 0.95),   # top-k AND top-p
        (16, 2, 0.0, 0, 1.0),    # greedy again, different bucket
    ]
    reqs = []
    for i, (plen, mnew, temp, k, p) in enumerate(specs):
        prompt = jax.random.randint(jax.random.fold_in(KEY, i), (plen,),
                                    0, vocab)
        reqs.append(Request(prompt=prompt, max_new_tokens=mnew,
                            temperature=temp, top_k=k, top_p=p, rid=i))
    return reqs


def test_continuous_matches_serve_engine_greedy(smoke_model):
    """Greedy decode through the continuous engine == ServeEngine.generate
    on the same prompt (the pre-existing engine is the reference)."""
    cfg, params = smoke_model
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    ref = ServeEngine(cfg=cfg, params=params, max_len=24,
                      cache_dtype=jnp.float32)
    out = ref.generate(prompts, max_new_tokens=6)
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=24,
                                   cache_dtype=jnp.float32)
    results, stats = eng.serve([Request(prompt=prompts[0],
                                        max_new_tokens=6, rid=0)])
    assert results[0]["tokens"] == [int(t) for t in np.asarray(out[0])]
    assert not results[0]["flagged"]
    assert stats["tokens"] == 6


def test_churn_bitwise_parity_and_single_compile(smoke_model):
    """The acceptance gate: a churning pool (staggered arrivals into 2
    slots, all sampling modes mixed) emits bitwise the same tokens per
    request as a single-slot engine serving each request alone — and the
    whole churn adds zero compiles to the warmed decode tick."""
    cfg, params = smoke_model
    base = jax.random.PRNGKey(7)
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=48,
                                   base_key=base)
    # warm the tick (and one prefill bucket); rid outside the churn range
    eng.serve([Request(prompt=jnp.zeros((4,), jnp.int32),
                       max_new_tokens=2, rid=999)])
    reqs = _requests(cfg.vocab_size)
    arrivals = [0, 0, 1, 3, 3, 6]
    with assert_compiles(0, tick=eng._tick):
        results, stats = eng.serve(reqs, arrival_ticks=arrivals)

    alone = ContinuousBatchingEngine(cfg, params, slots=1, max_len=48,
                                     base_key=base)
    for r in _requests(cfg.vocab_size):
        solo, _ = alone.serve([r])
        assert solo[r.rid]["tokens"] == results[r.rid]["tokens"], \
            f"request {r.rid} diverged under churn"
        assert len(results[r.rid]["tokens"]) == r.max_new_tokens

    # schedule accounting: admits respect arrivals and slot capacity
    for i, r in enumerate(reqs):
        res = results[r.rid]
        assert res["admitted_tick"] >= arrivals[i]
        assert res["finished_tick"] >= res["admitted_tick"]
    assert stats["occupied_slot_ticks"] <= stats["ticks"] * eng.slots


def test_sampled_tokens_in_range_and_reproducible(smoke_model):
    """Two serves of the same sampled request reproduce exactly (PRNG is
    keyed on rid + step, not on pool state or wall time)."""
    cfg, params = smoke_model
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=32)
    req = lambda: Request(prompt=jnp.arange(6, dtype=jnp.int32),
                          max_new_tokens=8, temperature=1.1, top_k=20,
                          rid=0)
    r1, _ = eng.serve([req()])
    r2, _ = eng.serve([req()])
    assert r1[0]["tokens"] == r2[0]["tokens"]
    assert all(0 <= t < cfg.vocab_size for t in r1[0]["tokens"])


def test_immediate_finish_single_token_request(smoke_model):
    """max_new_tokens=1 finishes at its admit tick: the first token comes
    from the prefill sample, no decode tick is owed."""
    cfg, params = smoke_model
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=16)
    results, stats = eng.serve([Request(prompt=jnp.arange(4, dtype=jnp.int32),
                                        max_new_tokens=1, rid=0)])
    res = results[0]
    assert len(res["tokens"]) == 1
    assert res["finished_tick"] == res["admitted_tick"]
    assert stats["occupied_slot_ticks"] == 0


def test_request_validation(smoke_model):
    cfg, params = smoke_model
    eng = ContinuousBatchingEngine(cfg, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.serve([Request(prompt=jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=0)])
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.serve([Request(prompt=jnp.arange(12, dtype=jnp.int32),
                           max_new_tokens=8)])


def test_continuous_engine_rejects_ssm_stacks():
    cfg = get_smoke("mamba2-370m")
    params = T.init_model(KEY, cfg)
    with pytest.raises(ValueError, match="attention-only"):
        ContinuousBatchingEngine(cfg, params, slots=1, max_len=16)


# ---------------------------------------------------------------------------
# non-finite flag propagation
# ---------------------------------------------------------------------------

def test_flags_isolate_poisoned_request(smoke_model):
    """A NaN embedding row poisons ONLY the requests whose prompt uses
    that token: their rows are flagged (every decode step re-raises via
    the NaN KV cache) and degrade to the in-range fallback, while a clean
    request in the same batch stays unflagged and decodes exactly the
    tokens it decodes on the clean weights.  Untied output projection so
    the poisoned table row cannot leak into every logit column.  The
    poisoned id is one the clean request never reads, in its prompt or
    in its own greedy continuation: decoding that token would poison it
    by its own input, not through the batch."""
    cfg, _ = smoke_model
    cfg = dataclasses.replace(cfg, tie_embeddings=False)
    params = T.init_model(KEY, cfg)
    clean = jnp.asarray([1, 2, 4, 5], jnp.int32)
    clean_out = ServeEngine(cfg=cfg, params=params, max_len=16,
                            cache_dtype=jnp.float32).generate(
        clean[None], max_new_tokens=4)[0].tolist()
    ceng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=16)
    clean_cont = ceng.serve([Request(prompt=clean, max_new_tokens=4,
                                     rid=1)])[0][1]["tokens"]
    touched = set(clean.tolist()) | set(clean_out) | set(clean_cont)
    bad_id = min(set(range(1, cfg.vocab_size)) - touched)

    def poison(tok):
        p = jax.tree.map(lambda x: x, params)
        p["embed"] = dict(params["embed"])
        p["embed"]["table"] = params["embed"]["table"].at[tok].set(jnp.nan)
        return p

    # Why the id must avoid the clean continuation: id 3 is a token the
    # clean request decodes and then reads back, so with id 3 poisoned it
    # is flagged when served ALONE, with no other row to leak from.
    assert 3 in clean_out[:-1]
    _, alone_flags = ServeEngine(cfg=cfg, params=poison(3), max_len=16,
                                 cache_dtype=jnp.float32).generate(
        clean[None], max_new_tokens=4, return_flags=True)
    assert bool(alone_flags[0])

    poisoned = poison(bad_id)
    # ServeEngine: flags are the union over prefill + every decode step
    eng = ServeEngine(cfg=cfg, params=poisoned, max_len=16,
                      cache_dtype=jnp.float32)
    prompts = jnp.stack([jnp.asarray([1, 2, bad_id, 4], jnp.int32),
                         clean])
    out, flags = eng.generate(prompts, max_new_tokens=4,
                              return_flags=True)
    assert bool(flags[0]) and not bool(flags[1])
    np.testing.assert_array_equal(np.asarray(out[0]), 0)  # fallback row
    assert out[1].tolist() == clean_out
    assert bool(((out >= 0) & (out < cfg.vocab_size)).all())

    # continuous engine: per-request ``flagged`` carries the same union
    ceng = ContinuousBatchingEngine(cfg, poisoned, slots=2, max_len=16)
    results, _ = ceng.serve([
        Request(prompt=prompts[0], max_new_tokens=4, rid=0),
        Request(prompt=prompts[1], max_new_tokens=4, rid=1)])
    assert results[0]["flagged"] and not results[1]["flagged"]
    assert results[0]["tokens"] == [0, 0, 0, 0]
    assert results[1]["tokens"] == clean_cont
    assert all(0 <= t < cfg.vocab_size for t in results[1]["tokens"])
