"""Tracing (``repro.obs``): the scope and span tables, the scopes the
compiled programs carry, and the serve engine's spans and stamps.

* A scope is metadata only: each program carries the table scopes it
  should, backward and rematerialised ops included, and compiling it with
  the scopes off gives the same instructions.
* The engine's host work lies in the listed spans, nested as listed; a
  device fetch happens only in ``serve.first_token`` (once per bucket) and
  ``serve.fetch`` (once per tick); each request's stamps are ordered.
* A profiler session changes no served token, and the spans land in its
  trace with their args.
"""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.models import causal_lm as LM
from repro.models import transformer as T
from repro.optim.adamw import OptimizerConfig
from repro.serve import ContinuousBatchingEngine, Request
from repro.train.state import make_train_state
from repro.train.step import make_train_step

KEY = jax.random.PRNGKey(0)
CFG = get_smoke("qwen3-1.7b")
MAX_LEN = 48

# the table scopes each compiled program holds, and no others
EXPECTED = {
    "train": {"spm", "attn", "ffn", "embed", "lm_head", "loss", "optimizer"},
    "prefill": {"spm", "attn", "ffn", "embed", "lm_head"},
    "tick": {"spm", "attn", "ffn", "embed", "lm_head", "sample"},
    "sample_first": {"sample"},
}


# ---- the tables -------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: obs.scope("attention"), lambda: obs.scoped("head"),
    lambda: obs.span("serve.decode"), lambda: obs.span("bench.tick")],
    ids=["scope", "scoped", "span", "span_prefix"])
def test_only_table_names_are_taken(make):
    with pytest.raises(ValueError, match="is not one of"):
        make()


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/jvp()/while/body/closed_call/attn/spm/mul", "spm"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/tanh", "attn"),
    ("jit(step)/transpose(jvp(loss))/mul", "loss"),
    ("jit(tick)/lm_head/dot_general", "lm_head"),
    ("jit(step)/jvp()/while/body/dynamic_slice", None),
    ("jit(embed)/jit(spm)/add", None),          # function names, no scope
    ("jit(f)/ffn/add;jit(f)/attn/mul", "ffn"),  # fused: the first path
    ("", None),
])
def test_scope_of_takes_the_innermost_table_scope(op_name, scope):
    assert obs.scope_of(op_name) == scope


def test_span_without_a_session_is_the_shared_no_op(tmp_path):
    assert obs.span("serve.tick", tick=3) is obs.span("serve.fetch")
    with jax.profiler.trace(str(tmp_path)):
        assert isinstance(obs.span("serve.tick", tick=3),
                          jax.profiler.TraceAnnotation)


# ---- scopes in the compiled programs ------------------------------------------

def _programs():
    """Lowerings of the smoke qwen3 train step and of the engine's
    prefill, tick and first-token sampler, each built anew per call."""
    params = T.init_model(KEY, CFG)
    state = jax.eval_shape(lambda: make_train_state(params))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(lambda p, b: LM.lm_loss(p, b, CFG),
                                   OptimizerConfig(), chaos_guard=True))
    eng = ContinuousBatchingEngine(CFG, params, slots=2, max_len=MAX_LEN)
    eng._reset()
    S, V = eng.slots, CFG.vocab_size
    logits = jnp.zeros((S, V), jnp.float32)
    keys, temp = eng._keys, eng._temp
    return {
        "train": lambda: step.lower(state, batch, 0.0),
        "prefill": lambda: eng._prefill.lower(
            params, jnp.zeros((2, 16), jnp.int32),
            jnp.array([9, 16], jnp.int32)),
        "tick": lambda: eng._tick.lower(
            params, eng._tok, eng._cache, eng._ci, eng._active, keys,
            eng._steps, temp, eng._topk, eng._topp),
        "sample_first": lambda: eng._sample_first.lower(
            logits, keys, temp, eng._topk, eng._topp),
    }


def _compile(lower):
    jax.clear_caches()
    return lower().compile().as_text()


@pytest.fixture(scope="module")
def hlo():
    return {k: _compile(v) for k, v in _programs().items()}


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("program", list(EXPECTED))
def test_program_carries_its_scopes(hlo, program):
    found = {obs.scope_of(n) for n in _op_names(hlo[program])} - {None}
    assert found == EXPECTED[program]


def test_backward_and_remat_ops_keep_their_scopes(hlo):
    names = _op_names(hlo["train"])
    for s in ("spm", "attn", "ffn", "lm_head", "loss"):
        assert any(obs.scope_of(n) == s and "transpose(" in n
                   for n in names), f"no backward op under {s}"
    for s in ("spm", "attn"):
        assert any(obs.scope_of(n) == s and "rematted_computation" in n
                   for n in names), f"no recomputed op under {s}"


def _instructions(text):
    """The instruction lines, metadata dropped and names numbered by first
    appearance (XLA's numeric suffixes may shift between compiles)."""
    names = {}
    out = []
    for ln in text.splitlines():
        if " = " not in ln:
            continue
        ln = re.sub(r", metadata=\{[^}]*\}", "", ln)
        out.append(re.sub(r"%[\w.-]+", lambda m: names.setdefault(
            m.group(0), f"%v{len(names)}"), ln))
    return out


@pytest.mark.parametrize("program", ["train", "tick"])
def test_scopes_change_no_instruction(hlo, program, monkeypatch):
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    bare = _compile(_programs()[program])
    assert not {obs.scope_of(n) for n in _op_names(bare)} - {None}
    assert _instructions(bare) == _instructions(hlo[program])


# ---- engine spans and stamps ----------------------------------------------------

def _requests():
    """Five requests on two slots: three buckets, greedy and sampled, a
    one-token request, and arrivals that queue."""
    specs = [(8, 4, 0.0), (5, 3, 0.8), (20, 1, 0.0), (12, 5, 0.7),
             (30, 3, 0.0)]
    return [Request(prompt=jax.random.randint(jax.random.fold_in(KEY, i),
                                              (n,), 0, CFG.vocab_size),
                    max_new_tokens=m, temperature=t, top_k=5, rid=i)
            for i, (n, m, t) in enumerate(specs)]


ARRIVALS = [0, 0, 0, 1, 6]


@pytest.fixture(scope="module")
def engine():
    return ContinuousBatchingEngine(CFG, T.init_model(KEY, CFG), slots=2,
                                    max_len=MAX_LEN)


class Recorder:
    """Stands in for ``obs.span``: records each span with its parent and
    args, and each ``jax.device_get`` with the span it was called in."""

    def __init__(self):
        self.spans, self.gets, self.stack = [], [], []

    def span(self, name, **args):
        assert name in obs.SPANS
        rec = self

        @contextlib.contextmanager
        def cm():
            rec.spans.append((name, rec.stack[-1] if rec.stack else None,
                              args))
            rec.stack.append(name)
            try:
                yield
            finally:
                rec.stack.pop()
        return cm()


@pytest.fixture(scope="module")
def recorded(engine):
    rec = Recorder()
    real_get = jax.device_get

    def get(x):
        rec.gets.append(rec.stack[-1] if rec.stack else None)
        return real_get(x)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs, "span", rec.span)
        mp.setattr(jax, "device_get", get)
        results, stats = engine.serve(_requests(), arrival_ticks=ARRIVALS)
    return rec, results, stats


def test_engine_spans_nest_as_listed(recorded):
    rec, _, stats = recorded
    top = [n for n, parent, _ in rec.spans if parent is None]
    assert set(top) == {"serve.schedule", "serve.admit", "serve.bookkeep",
                        "serve.tick", "serve.fetch"}
    assert {n for n, _, _ in rec.spans} == set(obs.SPANS)
    for name, parent, args in rec.spans:
        if parent is not None:
            assert parent == "serve.admit" and name in (
                "serve.pack", "serve.prefill", "serve.first_token",
                "serve.insert")
        if name == "serve.admit":
            assert set(args) == {"tick", "rids"} and args["rids"]
        if name == "serve.prefill":
            assert set(args) == {"bucket", "group"}
    # each admit: per bucket, pack -> prefill -> first_token -> insert
    kids = " ".join(n for n, p, _ in rec.spans if p == "serve.admit")
    assert re.fullmatch(r"(serve\.pack serve\.prefill serve\.first_token "
                        r"serve\.insert ?)+", kids)
    # each iteration: schedule, [admit], bookkeep, [tick, fetch, bookkeep];
    # the last schedule finds nothing left
    seq = " ".join(n.split(".")[1] for n in top)
    assert re.fullmatch(r"(schedule (admit )?bookkeep "
                        r"(tick fetch bookkeep )?)+schedule", seq)
    assert top.count("serve.schedule") == stats["ticks"] + 1


def test_device_fetches_only_in_first_token_and_fetch(recorded):
    rec, _, _ = recorded
    names = [n for n, _, _ in rec.spans]
    assert sorted(set(rec.gets)) == ["serve.fetch", "serve.first_token"]
    assert rec.gets.count("serve.first_token") == names.count(
        "serve.first_token")           # one per bucket of an admit
    assert rec.gets.count("serve.fetch") == names.count("serve.tick")


def test_stamps_are_ordered(recorded):
    _, results, _ = recorded
    for rid, r in results.items():
        assert r["arrival_s"] <= r["admit_s"] <= r["first_token_s"], rid
    # rid 3 arrives at tick 1 with both slots held: it waits in the queue
    assert results[3]["admit_s"] > results[3]["arrival_s"]
    assert results[3]["admitted_tick"] > ARRIVALS[3]


def test_profiler_session_changes_no_token(engine, recorded, tmp_path):
    _, plain, _ = recorded
    with jax.profiler.trace(str(tmp_path)):
        traced, _ = engine.serve(_requests(), arrival_ticks=ARRIVALS)
    assert {k: r["tokens"] for k, r in traced.items()} == \
        {k: r["tokens"] for k, r in plain.items()}
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    assert {ev.name for ev in events} == set(obs.SPANS)
    admits = [dict(ev.stats) for ev in events if ev.name == "serve.admit"]
    assert admits[0] == {"tick": 0, "rids": "[0, 1]"}
    ticks = [a["tick"] for a in admits]
    assert ticks == sorted(set(ticks))
