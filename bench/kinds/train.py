"""Training cells: the program's jitted train step, driven to a deadline.

Set-up builds one object — the step ``train()`` builds
(``make_train_step(lm_loss, OptimizerConfig, chaos_guard=True)`` under
``jax.jit``) over ``make_train_state`` and a ``DeterministicLoader`` —
and drives it from the seed through its first ``check_steps`` steps (the
first compiles).  Those readings are kept: each step's loss, each leaf's
norm of the first gradient as the optimizer took it (its first moment
over ``1 - beta1``), and each leaf's norm of the change after those
steps.  The same object then runs the window, fetching each step's
metrics as ``train()`` does.  After the window and with the program's
state freed, the plain reference repeats the first steps from the same
weights and batches, and the gaps between the two decide ``correct``.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Tuple

import adamw_reference
import checks
import spec
import traffic
import weights


def _leaf_names(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


@functools.lru_cache(maxsize=None)
def _norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree, scale):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                * scale for x in jax.tree.leaves(tree)]

    @jax.jit
    def diff_norms(a, b):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                            - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return norms, diff_norms


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    import jax
    vals = jax.device_get(_norms_fn()[0](tree, scale))
    return dict(zip(_leaf_names(tree), map(float, vals)))


def change_norms(a, b) -> Dict[str, float]:
    import jax
    vals = jax.device_get(_norms_fn()[1](a, b))
    return dict(zip(_leaf_names(a), map(float, vals)))


def batch_at(cell: spec.Cell, seed: int, step: int):
    """The reference's own derivation of the batch at ``step``."""
    import jax
    mix, shape = cell.traffic, spec.published(cell)
    fn = traffic.train_batch_fn(shape["vocab_size"], mix["seq"])
    key = jax.random.fold_in(jax.random.PRNGKey(traffic.seed32(seed)),
                             step)
    return fn(key, mix["batch"])


class Program:
    """The object set-up builds and the window drives."""

    def __init__(self, cell: spec.Cell, seed: int):
        import jax
        from repro.data.loader import DeterministicLoader
        from repro.models import causal_lm as LM
        from repro.models import transformer as T
        from repro.optim.adamw import OptimizerConfig
        from repro.train.state import make_train_state
        from repro.train.step import make_train_step
        mix = cell.traffic
        self.cfg = cfg = spec.program_config(cell)
        self.s32 = traffic.seed32(seed)
        self.structure = jax.eval_shape(
            lambda: T.init_model(jax.random.PRNGKey(0), cfg))
        self.state = make_train_state(
            weights.make_params(self.structure, self.s32))
        self.opt = OptimizerConfig(**mix["optimizer"])
        self.step_fn = jax.jit(make_train_step(
            lambda p, b: LM.lm_loss(p, b, cfg), self.opt, chaos_guard=True))
        self.loader = DeterministicLoader(
            traffic.train_batch_fn(cfg.vocab_size, mix["seq"]),
            mix["batch"], seed=self.s32)
        self.call = self.step_fn

    def step(self, s: int, span=None):
        """One step through the window's own call and feed."""
        import contextlib
        import jax
        span = span or (lambda name: contextlib.nullcontext())
        with span("bench.loader"):
            batch = self.loader.batch_at(s)
        with span("bench.step"):
            self.state, m = self.call(self.state, batch, 0.0)
        with span("bench.device_get"):
            return jax.device_get(m)

    def check_steps(self, n: int) -> dict:
        """Steps 1..n from the seed, with the readings the check keeps."""
        losses, grad = [], None
        for s in range(n):
            m = self.step(s)
            losses.append(float(m["loss"]))
            if s == 0:
                grad = leaf_norms(self.state["opt"]["mu"],
                                  1.0 / (1.0 - self.opt.beta1))
        p0 = weights.make_params(self.structure, self.s32)
        change = change_norms(self.state["params"], p0)
        return {"loss": losses, "grad": grad, "change": change}


def halve(x):
    """A planted fault: half of a (batch, seq) batch's tokens left out —
    the second half of the rows, or of each row's positions when the
    batch is one row — so the mean is taken over the rest."""
    if x.shape[0] > 1:
        return x[: x.shape[0] // 2]
    return x[:, : x.shape[1] // 2]


def reference_readings(cell: spec.Cell, seed: int, precision: str = "f32",
                       half_batch: bool = False) -> dict:
    """The first ``check_steps`` steps of the plain reference, from the
    same weights and batches (``half_batch``: the planted fault
    ``halve``)."""
    import jax
    from repro.models import transformer as T
    mix, shape = cell.traffic, spec.published(cell)
    ref = spec.reference(cell)
    cfg = spec.program_config(cell)
    structure = jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    s32 = traffic.seed32(seed)
    p = weights.make_params(structure, s32)
    st = adamw_reference.init_state(p)
    losses, grad, raw = [], None, None
    for s in range(mix["check_steps"]):
        b = batch_at(cell, seed, s)
        tok, lab = b["tokens"], b["labels"]
        if half_batch:
            tok, lab = halve(tok), halve(lab)
        loss, g = ref.loss_and_grads(shape, precision, p, tok, lab)
        losses.append(float(loss))
        p, st, gc = adamw_reference.step(mix["optimizer"], p, g, st, s + 1)
        if s == 0:
            grad, raw = leaf_norms(gc), leaf_norms(g)
        del g, gc
    del st
    p0 = weights.make_params(structure, s32)
    change = change_norms(p, p0)
    return {"loss": losses, "grad": grad, "change": change, "raw": raw}


def compare(prog: dict, ref: dict) -> Tuple[Dict[str, float], dict]:
    loss_gap = max(checks.rel_gap(a, b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    grad_gap, g_leaf = checks.worst_leaf_gap(prog["grad"], ref["grad"])
    keep = checks.moving_leaves(ref["raw"])
    change_gap, c_leaf = checks.worst_leaf_gap(prog["change"], ref["change"],
                                               keep)
    notes = {"grad_gap_leaf": g_leaf, "change_gap_leaf": c_leaf,
             "left_out_leaves": sorted(set(ref["raw"]) - set(keep)),
             "loss_program": prog["loss"], "loss_reference": ref["loss"]}
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "change_gap": change_gap}, notes)


def run(cell: spec.Cell, args, *, t_start, counter, tracer, span, log,
        faults) -> dict:
    import gc

    import harness
    mix = cell.traffic
    prog = Program(cell, args.seed)
    if faults.get("unchanged_state"):
        real = prog.step_fn
        prog.call = lambda st, b, p: (st, real(st, b, p)[1])
    if faults.get("half_batch"):
        real = prog.step_fn
        prog.call = lambda st, b, p: real(
            st, {k: halve(v) for k, v in b.items()}, p)
    readings = prog.check_steps(mix["check_steps"])
    log(f"set-up steps losses {readings['loss']}")

    # window: from the first timed step to the end of the last one
    s = mix["check_steps"]
    counter.on = True
    t0 = time.time()
    setup_s = t0 - t_start
    n = skipped = traced = 0
    step_s = []
    # a traced run also finishes its traced steps when they end past
    # the deadline (a slow step on a loaded host)
    while (time.time() < t0 + args.seconds
           or (tracer.enabled and not tracer.done)):
        if n == mix["trace_after_steps"]:
            tracer.start()
        ts = time.time()
        m = prog.step(s, span)
        step_s.append(time.time() - ts)
        traced += tracer.active
        skipped += int(m.get("skipped", 0) > 0)
        n += 1
        s += 1
        if n == mix["trace_after_steps"] + mix["trace_steps"]:
            tracer.stop()
    t1 = time.time()
    counter.on = False
    tracer.stop()
    window = t1 - t0
    tokens = n * mix["batch"] * mix["seq"]
    dev = harness.device_info(cell.chips)
    log(f"window {window:.3f}s steps {n} tokens/s {tokens / window:.1f} "
        f"setup {setup_s:.2f}s")
    log(harness.stall_note("step", step_s))

    prog.state = prog.call = prog.step_fn = None
    del prog
    gc.collect()
    t_ref = time.time()
    ref = reference_readings(cell, args.seed)
    numbers, notes = compare(readings, ref)
    notes["reference_s"] = round(time.time() - t_ref, 3)
    return {
        "e2e": {"train_tokens_per_s": tokens / window, "setup_s": setup_s},
        "attempted": n, "failed": skipped, "device": dev,
        "numbers": numbers, "notes": notes,
        "host": {"steps_traced": traced, "batch": mix["batch"],
                 "seq": mix["seq"], "tokens_per_s": tokens / window,
                 "window_s": window},
    }
