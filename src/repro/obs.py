"""Tracing: named scopes on the model's layers, host spans in the serve
engine.

Both land in one profiler session (``jax.profiler.start_trace`` /
``stop_trace``, or ``jax.profiler.trace``), on the clock the device ops
are stamped with:

* ``scope(name)`` is a ``jax.named_scope`` from the table ``SCOPES``
  (``scoped(name)`` puts a whole function under one).  It
  only adds to the HLO ``op_name`` metadata of the ops traced under it,
  forward, backward and rematerialised alike: no instruction and no number
  changes.  An op belongs to the innermost table scope in its ``op_name``
  (``scope_of``); an op under none is unscoped.
* ``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` from the
  table ``SPANS``, an interval on the host timeline whose args (request
  ids, bucket, group size, tick) become the event's stats.  With no
  session open it returns a shared no-op context, so the engine pays a
  name check and one call per span.

Nothing here waits on the device.  The span tree, the per-request stamps
and how to take a trace are in docs/serving.md.
"""

from __future__ import annotations

import contextlib
import functools
import re
from typing import Callable, Optional

import jax

__all__ = ["SCOPES", "SPANS", "scope", "scoped", "span", "scope_of"]

SCOPES = ("spm", "attn", "ffn", "moe", "ssm", "embed", "lm_head", "loss",
          "sample", "optimizer")

SPANS = ("serve.schedule", "serve.admit", "serve.pack", "serve.prefill",
         "serve.first_token", "serve.insert", "serve.tick", "serve.fetch",
         "serve.bookkeep")

_SCOPE_SET = frozenset(SCOPES)
_SPAN_SET = frozenset(SPANS)
_NULL = contextlib.nullcontext()
_Annotation = jax.profiler.TraceAnnotation


def scope(name: str):
    """``jax.named_scope(name)`` for a name in ``SCOPES``; any other name
    is a ``ValueError``."""
    if name not in _SCOPE_SET:
        raise ValueError(f"scope {name!r} is not one of {SCOPES}")
    return jax.named_scope(name)


def scoped(name: str) -> Callable:
    """Decorator: the function's whole body runs under ``scope(name)``."""
    if name not in _SCOPE_SET:
        raise ValueError(f"scope {name!r} is not one of {SCOPES}")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def span(name: str, **args):
    """A host span named ``name`` (one of ``SPANS``) with ``args`` as its
    stats, while a profiler session is open; a no-op context otherwise."""
    if name not in _SPAN_SET:
        raise ValueError(f"span {name!r} is not one of {SPANS}")
    if not _Annotation.is_enabled():
        return _NULL
    return _Annotation(name, **args)


# one op_name path component: transformation wrappers around a name, as in
# ``transpose(jvp(attn))``; a ``jit(...)``/``pjit(...)`` wraps a function's
# name, never a scope
_PART = re.compile(r"^((?:[\w.-]+\()*)([^()]*)\)*$")
_CALLS = frozenset(("jit", "pjit"))


def scope_of(op_name: str) -> Optional[str]:
    """The innermost table scope in an HLO ``op_name``
    (``jit(step)/transpose(jvp(attn))/spm/mul`` -> ``"spm"``), or ``None``
    for an unscoped op.  A fused op's ``op_name`` may join several paths
    with ``;``: the first one counts."""
    found = None
    for part in op_name.split(";", 1)[0].split("/"):
        m = _PART.match(part)
        if m is None:
            continue
        wrappers, inner = m.groups()
        if inner in _SCOPE_SET and _CALLS.isdisjoint(wrappers.split("(")):
            found = inner
    return found
