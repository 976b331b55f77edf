"""Seeded generators: training batches, serving requests and arrivals.

A serving mix fixes its schedule: the prompt and output lengths, the
greedy flags and the arrival gaps are stratified draws (``stratified``)
put in an order drawn from the mix's own ``schedule_seed``.  A run's
``--seed`` draws the token ids (and the weights and sampling keys
elsewhere), never the schedule: the tail of a queue at four fifths of
its knee turns on the order of its arrivals, so runs with different
seeds do the same work in the same order.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import List, Tuple

import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` from any whole ``seed``
    (PRNGKey keeps only the low 32 bits of a larger one)."""
    h = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def stratified(rng: np.random.Generator, n: int, ppf,
               block: int = 0) -> np.ndarray:
    """``n`` values in blocks of ``block`` (all ``n`` when 0): each block
    holds ``ppf`` at the midpoints of ``block`` equal-probability strata,
    in an order drawn from ``rng``.  Any stretch of whole blocks holds
    the distribution exactly, so a window that serves the first few
    blocks sees the mix's own sizes and rate."""
    b = block or n
    vals = ppf((np.arange(b) + 0.5) / b)
    return np.concatenate([rng.permutation(vals)
                           for _ in range(-(-n // b))])[:n]


def lognormal_lengths(rng, n: int, median: float, sigma: float,
                      lo: int, hi: int, block: int = 0) -> np.ndarray:
    from scipy.special import ndtri
    vals = stratified(rng, n, lambda u: median * np.exp(sigma * ndtri(u)),
                      block)
    return np.clip(np.round(vals), lo, hi).astype(np.int64)


def stratified_arrivals(rng, n: int, rate: float, block: int = 0) -> list:
    """Poisson arrivals by the rule of the program's
    ``benchmarks/serve_bench.py`` (arrival tick = floor of the cumulative
    exponential gaps at ``rate`` requests per tick), except that the
    gaps are stratified draws (``stratified``)."""
    gaps = stratified(rng, n, lambda u: -np.log1p(-u) / rate, block)
    return [int(t) for t in np.floor(np.cumsum(gaps))]


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    temperature: float
    top_k: int
    top_p: float

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def serve_requests(mix: dict, vocab: int, seed: int
                   ) -> Tuple[List[ServeRequest], List[int]]:
    """The cell's request list and arrival ticks: the schedule from the
    mix alone, the prompts' token ids from ``seed``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    tokens = np.random.default_rng(int(seed))
    n, b = mix["requests"], mix["block"]
    p, o = mix["prompt"], mix["output"]
    plens = lognormal_lengths(rng, n, p["median"], p["sigma"], p["min"],
                              p["max"], b)
    olens = lognormal_lengths(rng, n, o["median"], o["sigma"], o["min"],
                              o["max"], b)
    greedy = stratified(rng, n, lambda u: u < mix["greedy_share"], b)
    s = mix["sampled"]
    arrivals = stratified_arrivals(rng, n, mix["arrivals"]["rate_per_tick"],
                                   b)
    reqs = []
    for i in range(n):
        prompt = tokens.integers(0, vocab, size=int(plens[i]),
                                 dtype=np.int32)
        g = bool(greedy[i])
        reqs.append(ServeRequest(
            rid=i, prompt=prompt, max_new_tokens=int(olens[i]),
            temperature=0.0 if g else float(s["temperature"]),
            top_k=0 if g else int(s["top_k"]),
            top_p=1.0 if g else float(s["top_p"])))
    return reqs, arrivals


def train_batch_fn(vocab: int, seq: int):
    """``batch_fn(key, batch)`` for ``DeterministicLoader``: uniform token
    ids, labels the next token of the same row.  Jitted, so a batch is
    made on the device."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def make(key, batch):
        tok = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}

    return make
