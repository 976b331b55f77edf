"""Serving cells: ``ContinuousBatchingEngine.serve`` over the cell's
seeded requests and arrival ticks.

Arrivals are in engine ticks (an open loop in tick time).  The benchmark
installs wrappers on the engine instance's compiled callables (``_prefill``,
``_sample_first``, ``_insert``, ``_tick``) and on ``_admit``; they stamp
the host clock (each waits for its call's result, which the engine fetches
next anyway) and write host spans for the trace.  A missing hook point is
an error.

Set-up serves the whole request list once with the model calls stubbed
after their first call at each shape, so every program and every eager
operation the list needs is compiled.  The window serves it again from
the start; once ``--seconds`` have passed, serving goes on until every
request that arrived in the window has finished (the drain), then stops.

Metrics: ``ttft_p95_s`` over the requests that arrived in the window, from
the wall time their arrival tick began to their first token on the host;
``itl_p95_ms`` over the gaps between consecutive tokens of a request
whose later token came in the window; ``serve_tokens_per_s``, the tokens
produced in the window over its length.  Correctness: for a seeded
sample of finished greedy requests (the longest among them), the plain
reference runs over each prompt with its served tokens.  Two numbers
decide: the widest gap by which a served token's reference logit lies
below the reference's best (``logit_gap``), and the widest relative L2
distance between the logits the program's prefill returned for the
request's first token and the reference's (``first_logit_err``).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import checks
import spec
import traffic
import weights

HOOKS = ("_prefill", "_sample_first", "_insert", "_tick", "_admit")


class StopServing(Exception):
    pass


class EngineProbe:
    """Wrappers on one engine instance.  ``stub=True`` (set-up): each
    model call runs for real once per shape, then returns placeholders of
    that shape, so the host path and every shape it meets are compiled
    without the model's work."""

    def __init__(self, eng, span, faults=None):
        import jax
        for h in HOOKS:
            if not hasattr(eng, h):
                raise RuntimeError(f"engine has no hook point {h!r}")
        self.eng, self.span, self.jax = eng, span, jax
        self.faults = faults or {}
        self.real = {h: getattr(eng, h) for h in HOOKS}
        for h in HOOKS:
            setattr(eng, h, getattr(self, h))
        self.stub = False
        self.seen = set()
        self.shapes = set()
        self._shape_of = {}
        self.arrival = {}
        self.reset(float("inf"), 0.0)

    def reset(self, deadline: float, t0: float):
        self.deadline = deadline
        self.closed_at_tick = None
        self.cur_tick = 0
        self.last_exit = t0
        self.tick_start: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.prefills: List[tuple] = []      # (t0, t1, G, bucket, real)
        self.ticks: List[tuple] = []         # (t0, t1, admit_in_tick)
        self.admit_tick = -1
        self.results = None
        self.batch = {}
        self.batch_list = []
        self.first_logits = {}              # rid -> (logits, row)
        self.last_sample = 0.0
        self.drain_limit = float("inf")

    # ---- helpers --------------------------------------------------------
    def _block(self, x):
        self.jax.block_until_ready(x)

    def _begin_tick(self, k: int):
        if k not in self.tick_start:
            self.tick_start[k] = self.last_exit

    def arrived_unfinished(self):
        if self.closed_at_tick is None or self.results is None:
            return True
        return any(r["finished_tick"] is None for rid, r in
                   self.results.items()
                   if self.arrival[rid] < self.closed_at_tick)

    # ---- hooks ------------------------------------------------------------
    def _admit(self, batch, tick_idx, results):
        self.results = results
        self.cur_tick = tick_idx
        self.admit_tick = tick_idx
        self._begin_tick(tick_idx)
        self.batch = {slot: req for slot, req in batch}
        self.batch_list = [req for _, req in batch]
        with self.span("bench.admit"):
            self.real["_admit"](batch, tick_idx, results)
        self.last_exit = time.time()

    def _prefill(self, params, toks, lens):
        key = ("prefill", tuple(toks.shape))
        self.shapes.add(tuple(toks.shape))
        if self.stub and key in self.seen:
            out = self._zeros(key)
        else:
            t = time.time()
            with self.span("bench.prefill"):
                out = self.real["_prefill"](params, toks, lens)
                self._block(out)
            if self.stub:
                self.seen.add(key)
                self._shape_of[key] = self.jax.tree.map(
                    lambda x: self.jax.ShapeDtypeStruct(x.shape, x.dtype),
                    out)
            else:
                self.prefills.append((t, time.time(), toks.shape[0],
                                      toks.shape[1],
                                      [int(x) for x in np.asarray(lens)]))
                # the group's last-position logits stay on the device
                # for the check (members in the engine's bucket order)
                group = [r for r in self.batch_list if self.eng._bucket(
                    len(r.prompt)) == toks.shape[1]]
                for g, r in enumerate(group):
                    if r.temperature <= 0.0:
                        self.first_logits[r.rid] = (out[0], g)
        self.last_exit = time.time()
        return out

    def _zeros(self, key):
        jnp = self.jax.numpy
        return self.jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                                 self._shape_of[key])

    def _sample_first(self, *a):
        with self.span("bench.sample_first"):
            out = self.real["_sample_first"](*a)
            self._block(out)
        self.last_sample = self.last_exit = time.time()
        return out

    def _insert(self, cache, pcache, row, slot):
        key = ("insert", self.jax.tree.leaves(pcache)[0].shape)
        if self.stub and key in self.seen:
            out = cache
        else:
            out = self.real["_insert"](cache, pcache, row, slot)
            self.seen.add(key)
        if not self.stub:
            req = self.batch[int(slot)]
            self.token_times[req.rid] = [self.last_sample]
        return out

    def _tick(self, *a):
        k = self.cur_tick
        now = time.time()
        if not self.stub:
            if self.closed_at_tick is None and now >= self.deadline:
                self.closed_at_tick = k
            if self.closed_at_tick is not None and (
                    not self.arrived_unfinished()
                    or now >= self.deadline + self.drain_limit):
                raise StopServing()
        self._begin_tick(k)
        key = ("tick",)
        if self.stub and key in self.seen:
            tok, _, cache, ci, steps = a[1], None, a[2], a[3], a[6]
            out = (tok, self._bad, cache, ci, steps)
        else:
            rids = [r.rid for r in self.eng._slot_req if r is not None]
            t = time.time()
            with self.span("bench.tick"):
                out = self.real["_tick"](*a)
                if self.faults.get("alter_token"):
                    out = (out[0] + 1,) + tuple(out[1:])
                self._block(out)
            t1 = time.time()
            if self.stub:
                self.seen.add(key)
                self._bad = out[1]
            else:
                self.ticks.append((t, t1, self.admit_tick == k))
                for rid in rids:
                    self.token_times[rid].append(t1)
        self.cur_tick = k + 1
        self.last_exit = time.time()
        return out


def _engine(cell, params, seed32):
    import jax
    from repro.serve.engine import ContinuousBatchingEngine
    mix = cell.traffic
    return ContinuousBatchingEngine(
        spec.program_config(cell), params, slots=mix["slots"],
        max_len=mix["max_len"], base_key=jax.random.PRNGKey(seed32))


def _requests(cell, seed):
    from repro.serve.engine import Request
    shape = spec.published(cell)
    reqs, arrivals = traffic.serve_requests(cell.traffic,
                                            shape["vocab_size"], seed)
    prog = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p, rid=r.rid) for r in reqs]
    return reqs, prog, arrivals


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) \
        if len(xs) else float("nan")


def served(probe, reqs, arrivals, t0, t_close):
    """End-to-end numbers from the probe's stamps."""
    K = (probe.closed_at_tick if probe.closed_at_tick is not None
         else float("inf"))
    starts = sorted(probe.tick_start.items())
    ttft, itl, tokens = [], [], 0
    attempted = failed = 0
    for r in reqs:
        times = probe.token_times.get(r.rid, [])
        tokens += sum(t0 <= t <= t_close for t in times)
        itl += [b - a for a, b in zip(times, times[1:]) if t0 <= b <= t_close]
        a = arrivals[r.rid]
        if a >= K:
            continue
        arr_t = next((t for k, t in starts if k >= a), None)
        if arr_t is None:
            continue          # the list ran out before this tick
        attempted += 1
        if not times:
            failed += 1
        else:
            ttft.append(times[0] - arr_t)
    return ttft, itl, tokens, attempted, failed


def sample_requests(probe, reqs, seed, n):
    """A seeded sample of finished greedy requests, with the longest."""
    done = [r for r in reqs if r.greedy and probe.results is not None
            and probe.results[r.rid]["finished_tick"] is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.max_new_tokens)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = list(rng.choice(len(rest), size=min(n - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_readings(cell, sample, served_tokens, first_logits,
                       precisions=("f32",), params=None):
    """Per precision, what the reference reads over the sample.

    ``gap``: at every served position, the f32 reference's best logit
    minus its logit of the token served there (for a lower precision,
    of the token that precision puts first).  ``first_err``: per request,
    the relative L2 distance of the first token's logits from the f32
    reference's, for "f32" the program's own (``first_logits``, what its
    prefill returned), for a lower precision that precision's."""
    import jax
    import jax.numpy as jnp
    shape = spec.published(cell)
    ref = spec.reference(cell)
    max_len = cell.traffic["max_len"]
    gaps = {p: [] for p in precisions}
    errs = {p: [] for p in precisions}

    def rel_l2(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    for r in sample:
        toks = served_tokens[r.rid]
        m = len(toks)
        seq = np.zeros((max_len,), np.int32)
        P = len(r.prompt)
        seq[:P] = r.prompt
        seq[P:P + m - 1] = toks[:-1]
        pos = np.arange(P - 1, P - 1 + m, dtype=np.int32)
        pos_pad = np.full((cell.traffic["output"]["max"],), P - 1, np.int32)
        pos_pad[:m] = pos
        f32 = np.asarray(jax.device_get(ref.logits_at(
            shape, "f32", params, jnp.asarray(seq),
            jnp.asarray(pos_pad))))[:m]
        for p in precisions:
            if p == "f32":
                gaps[p].append(checks.served_gaps(f32, np.asarray(toks)))
                errs[p].append(rel_l2(first_logits[r.rid], f32[0]))
            else:
                lo = np.asarray(jax.device_get(ref.logits_at(
                    shape, p, params, jnp.asarray(seq),
                    jnp.asarray(pos_pad))))[:m]
                gaps[p].append(checks.served_gaps(f32, lo.argmax(-1)))
                errs[p].append(rel_l2(lo[0], f32[0]))
    return {p: {"gap": (np.concatenate(gaps[p]) if gaps[p]
                        else np.zeros(0)),
                "first_err": np.asarray(errs[p])} for p in precisions}


def numbers_of(reading) -> Dict[str, float]:
    """The cell's compared numbers from one precision's reading."""
    nan = float("nan")
    return {"logit_gap": float(reading["gap"].max())
            if reading["gap"].size else nan,
            "first_logit_err": float(reading["first_err"].max())
            if reading["first_err"].size else nan}


class Server:
    """The engine the window drives, with its probe, warmed for the
    cell's request list."""

    def __init__(self, cell: spec.Cell, seed: int, span, faults=None):
        import jax
        from repro.models import transformer as T
        self.cell = cell
        cfg = spec.program_config(cell)
        self.structure = jax.eval_shape(
            lambda: T.init_model(jax.random.PRNGKey(0), cfg))
        s32 = traffic.seed32(seed)
        self.params = weights.make_params(self.structure, s32)
        self.eng = _engine(cell, self.params, s32)
        self.probe = EngineProbe(self.eng, span, faults)
        self.seed = seed
        reqs, preqs, arrivals = _requests(cell, seed)
        self.probe.arrival = dict(zip((r.rid for r in reqs), arrivals))
        # set-up: the whole list with model calls stubbed after their first
        self.probe.stub = True
        self.eng.serve(preqs, arrival_ticks=arrivals)
        self.probe.stub = False

    def reseed(self, seed: int):
        """New weights and requests for ``seed`` on the warmed engine
        (every seed's list has the same lengths)."""
        import jax
        s32 = traffic.seed32(seed)
        self.params = self.eng.params = None
        self.params = weights.make_params(self.structure, s32)
        self.eng.params = self.params
        self.eng.base_key = jax.random.PRNGKey(s32)
        self.seed = seed

    def window(self, seconds: float, tracer=None, trace_after=0.0,
               trace_s=0.0) -> dict:
        """Serve the list from the start for ``seconds``, then drain."""
        probe, mix = self.probe, self.cell.traffic
        reqs, preqs, arrivals = _requests(self.cell, self.seed)
        probe.arrival = dict(zip((r.rid for r in reqs), arrivals))
        t0 = time.time()
        probe.reset(t0 + seconds, t0)
        probe.drain_limit = mix["drain_limit_s"]
        if tracer is not None and tracer.enabled:
            probe.trace_from = t0 + trace_after
            probe.trace_to = probe.trace_from + trace_s
            _trace_hooks(probe, tracer)
        try:
            self.eng.serve(preqs, arrival_ticks=arrivals)
            complete = True
        except StopServing:
            complete = False
        t_end = time.time()
        if tracer is not None:
            tracer.stop()
            self.eng._tick = probe._tick
        t_close = min(t0 + seconds, t_end)
        ttft, itl, tokens, attempted, failed = served(probe, reqs, arrivals,
                                                      t0, t_close)
        sample = sample_requests(probe, reqs, self.seed,
                                 mix["check_requests"])
        import jax
        first = {}
        for r in sample:
            arr, g = probe.first_logits[r.rid]
            first[r.rid] = np.asarray(jax.device_get(arr), np.float32)[g]
        probe.first_logits = {}
        return {"t0": t0, "t_close": t_close, "t_end": t_end,
                "complete": complete, "ttft": ttft, "itl": itl,
                "tokens": tokens, "attempted": attempted, "failed": failed,
                "reqs": reqs,
                "served": {rid: list(r["tokens"]) for rid, r in
                           (probe.results or {}).items()},
                "sample": sample, "first_logits": first}

    def free_engine(self):
        self.eng._cache = None
        self.eng = self.probe = None


def run(cell: spec.Cell, args, *, t_start, counter, tracer, span, log,
        faults) -> dict:
    import gc

    import harness
    mix = cell.traffic
    srv = Server(cell, args.seed, span, faults)
    log(f"set-up shapes {sorted(srv.probe.shapes)}")
    setup_s = time.time() - t_start
    counter.on = True
    w = srv.window(args.seconds, tracer, mix["trace_after_s"],
                   mix["trace_s"])
    counter.on = False
    window = w["t_close"] - w["t0"]
    dev = harness.device_info(cell.chips)
    log(f"window {window:.3f}s drain {w['t_end'] - w['t_close']:.3f}s "
        f"requests {w['attempted']} tokens {w['tokens']} "
        f"list_exhausted {w['complete']} setup {setup_s:.2f}s")
    starts = sorted(t for t in srv.probe.tick_start.values()
                    if w["t0"] <= t <= w["t_close"])
    log(harness.stall_note("tick", np.diff(starts).tolist()))
    host = layer_stamps(srv.probe, w["reqs"], tracer, window)
    srv.free_engine()
    gc.collect()
    t_ref = time.time()
    reading = reference_readings(cell, w["sample"], w["served"],
                                 w["first_logits"],
                                 params=srv.params)["f32"]
    n_tok = int(reading["gap"].size)
    return {
        "e2e": {"ttft_p95_s": percentile(w["ttft"], 95),
                "itl_p95_ms": 1e3 * percentile(w["itl"], 95),
                "serve_tokens_per_s": w["tokens"] / window,
                "setup_s": setup_s},
        "attempted": w["attempted"], "failed": w["failed"], "device": dev,
        "numbers": numbers_of(reading),
        "notes": {"checked_requests": len(w["sample"]),
                  "checked_tokens": n_tok,
                  "ttft_samples": len(w["ttft"]),
                  "itl_samples": len(w["itl"]),
                  "drain_s": round(w["t_end"] - w["t_close"], 3),
                  "reference_s": round(time.time() - t_ref, 3)},
        "host": host,
    }


def _trace_hooks(probe, tracer):
    """Start and stop the profiler at tick boundaries inside the window."""
    inner = probe._tick

    def tick(*a):
        now = time.time()
        if not tracer.active and not tracer.done and now >= probe.trace_from:
            tracer.start()
        elif tracer.active and now >= probe.trace_to:
            tracer.stop()
        return inner(*a)
    probe.eng._tick = tick


def layer_stamps(probe, reqs, tracer, window):
    """What the per-layer readers take from the host stamps: every
    prefill and tick of the window, and the prompt lengths and decode
    positions of the work done while the profiler ran."""
    lo = getattr(tracer, "t_start", None)
    hi = getattr(tracer, "t_stop", None)
    inside = (lambda t: lo is not None and hi is not None and lo <= t <= hi)
    prompt_lens, positions = [], []
    for p in probe.prefills:
        if inside(p[1]):
            prompt_lens += p[4]
    for r in reqs:
        times = probe.token_times.get(r.rid, [])
        P = len(r.prompt)
        positions += [P + i - 1 for i, t in enumerate(times)
                      if i >= 1 and inside(t)]
    return {"prefills": [(b - a, g, bk, sum(ls))
                         for a, b, g, bk, ls in probe.prefills],
            "ticks": [(b - a, adm) for a, b, adm in probe.ticks],
            "traced_prompt_lens": prompt_lens,
            "traced_decode_positions": positions,
            "traced_s": (hi - lo) if inside(lo or 0) else None,
            "window_s": window}
