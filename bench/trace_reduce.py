"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

The profiler writes an ``.xplane.pb`` (an XSpace proto) under
``<dir>/plugins/profile/<t>/``.  ``load`` turns it into plain ``Span``
lists: the device operations of each accelerator plane (line "XLA Ops"),
with the XLA module (jitted program) each ran in and its program scope,
and the benchmark's own host spans (``TraceAnnotation`` names starting
with ``bench.``).  Everything else here works on those lists, so it is
tested on synthetic spans without a chip.

An op's scope is the innermost name of ``SCOPES`` in the HLO ``op_name``
that the program's ``jax.named_scope``s left, which the trace keeps as
the ``tf_op`` stat of the op's event metadata.  The table and the rule
are the benchmark's own copy, so that a change to the program cannot
move what the benchmark reads.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import heapq
import importlib.util
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIX = "bench."

SCOPES = ("spm", "attn", "ffn", "moe", "ssm", "embed", "lm_head", "loss",
          "sample", "optimizer")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float          # seconds, on the trace's common clock
    end: float
    module: str = ""      # XLA module of a device op ("" for host spans)
    scope: str = ""       # innermost name of SCOPES in its op_name, or ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Span]]     # device plane name -> ops
    host_spans: List[Span]
    stand_in: bool = False                # host CPU ops stand in (tests)

    def window(self, name: str = "bench.window") -> Tuple[float, float]:
        """The interval of the host span ``name`` (the traced stretch)."""
        spans = [s for s in self.host_spans if s.name == name]
        if not spans:
            raise ValueError(f"trace holds no host span {name!r}")
        return min(s.start for s in spans), max(s.end for s in spans)


# one op_name path component: transformation wrappers around a name, as in
# ``transpose(jvp(attn))``; a ``jit(...)``/``pjit(...)`` wraps a function's
# name, never a scope
_PART = re.compile(r"^((?:[\w.-]+\()*)([^()]*)\)*$")
_CALLS = frozenset(("jit", "pjit"))


def scope_of(op_name_path: str) -> str:
    """The innermost name of ``SCOPES`` in an HLO ``op_name``
    (``jit(step)/transpose(jvp(attn))/spm/mul`` -> ``"spm"``), or ``""``.
    A fused op's ``op_name`` may join several paths with ``;``: the first
    one counts."""
    found = ""
    for part in op_name_path.split(";", 1)[0].split("/"):
        m = _PART.match(part)
        if m is None:
            continue
        wrappers, inner = m.groups()
        if inner in SCOPES and _CALLS.isdisjoint(wrappers.split("(")):
            found = inner
    return found


def op_name(event_name: str) -> str:
    """An op's own name from its event's name, which on the TPU is the
    whole HLO instruction (``%fusion.3 = f32[8] fusion(%spm_call.2)``):
    its operands must not make it match a kernel's pattern."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@functools.lru_cache(maxsize=None)
def _xplane_pb2():
    """The XSpace message classes: ``xplane_pb2.py`` beside this file,
    which needs the protobuf runtime and nothing else."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except ImportError as e:
        raise ImportError(f"reading a trace needs the protobuf package, "
                          f"6.31 or later, for {path}: {e}") from e
    return mod


class _Plane:
    """One XPlane's names and stats, read as ``jax.profiler.ProfileData``
    reads them: an event's name is its metadata's, its start and length
    whole nanoseconds."""

    def __init__(self, plane):
        self.plane = plane
        self.stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        self._scopes: Dict[int, str] = {}

    def _value(self, st) -> str:
        kind = st.WhichOneof("value")
        if kind == "ref_value":
            return self.stat_names.get(st.ref_value, "")
        return str(getattr(st, kind)) if kind else ""

    def stat(self, stats, key: str) -> Optional[str]:
        for st in stats:
            if self.stat_names.get(st.metadata_id) == key:
                return self._value(st)
        return None

    def name(self, ev) -> str:
        return self.plane.event_metadata[ev.metadata_id].name

    def span(self, line, ev, name: str, **kw) -> Span:
        start = line.timestamp_ns + ev.offset_ps // 1000
        return Span(name, start * 1e-9,
                    (start + ev.duration_ps // 1000) * 1e-9, **kw)

    def op(self, line, ev) -> Span:
        """A device op with its module (the event's ``hlo_module`` stat)
        and its scope (from the ``tf_op`` stat of its metadata)."""
        mid = ev.metadata_id
        if mid not in self._scopes:
            self._scopes[mid] = scope_of(self.stat(
                self.plane.event_metadata[mid].stats, "tf_op") or "")
        return self.span(line, ev, op_name(self.name(ev)),
                         module=self.stat(ev.stats, "hlo_module") or "",
                         scope=self._scopes[mid])


def load(trace_dir: str, cpu_stand_in: bool = False) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``.  A trace with
    no accelerator plane that holds device operations is an error, unless
    ``cpu_stand_in`` (tests on the CPU only) lets the XLA ops of the
    host's threads stand in."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    space = _xplane_pb2().XSpace()
    with open(files[-1], "rb") as f:
        space.ParseFromString(f.read())
    device_ops: Dict[str, List[Span]] = {}
    host: List[Span] = []
    cpu_ops: List[Span] = []
    for plane in space.planes:
        pl = _Plane(plane)
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [pl.op(line, ev) for line in plane.lines
                   if line.name == "XLA Ops" for ev in line.events]
            modules = [pl.span(line, ev, pl.name(ev))
                       for line in plane.lines if line.name == "XLA Modules"
                       for ev in line.events]
            if ops:   # a plane with no op is no device that ran (an
                # idle TPU plugin loaded beside the CPU backend has one)
                device_ops[plane.name] = _attach_modules(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = pl.name(ev)
                    if name.startswith(HOST_PREFIX):
                        host.append(pl.span(line, ev, name))
                    elif (cpu_stand_in and plane.name == "/host:CPU"
                          and pl.stat(ev.stats, "hlo_op") is not None):
                        cpu_ops.append(pl.op(line, ev))
    if not device_ops:
        if not cpu_stand_in:
            raise RuntimeError(f"the trace under {trace_dir} has no "
                               f"accelerator plane; its host ops are no "
                               f"device numbers")
        device_ops["/host:CPU"] = cpu_ops
    return Trace(device_ops, host, stand_in="/host:CPU" in device_ops)


def _attach_modules(ops: List[Span], modules: List[Span]) -> List[Span]:
    """Give each op without an ``hlo_module`` stat the module whose
    interval holds its start."""
    if not modules or all(o.module for o in ops):
        return ops
    modules = sorted(modules, key=lambda m: m.start)
    out, j = [], 0
    for o in sorted(ops, key=lambda s: s.start):
        if o.module:
            out.append(o)
            continue
        while j + 1 < len(modules) and modules[j + 1].start <= o.start:
            j += 1
        m = modules[j]
        name = m.name if m.start <= o.start < m.end else ""
        out.append(dataclasses.replace(o, module=name))
    return out


def clip(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    out = []
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            out.append(dataclasses.replace(s, start=a, end=b))
    return out


def union(spans: Iterable[Span]) -> List[Tuple[float, float]]:
    """Merged busy intervals, sorted."""
    iv = sorted((s.start, s.end) for s in spans)
    out: List[Tuple[float, float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(ops: Iterable[Span], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(ops, lo, hi)))


def matching(ops: Iterable[Span], pattern: str,
             module_pattern: Optional[str] = None) -> List[Span]:
    """Ops whose name matches ``pattern`` (and whose module matches
    ``module_pattern``), both case-insensitive regexes searched anywhere."""
    rx = re.compile(pattern, re.I)
    mx = re.compile(module_pattern, re.I) if module_pattern else None
    return [o for o in ops if rx.search(o.name)
            and (mx is None or mx.search(o.module))]


def op_seconds(ops: Iterable[Span]) -> float:
    """Summed device durations (overlapping ops counted each)."""
    return sum(o.dur for o in ops)


def idle_gaps(ops: Iterable[Span], host: Iterable[Span], lo: float,
              hi: float) -> List[Tuple[str, float]]:
    """Device idle time inside [lo, hi], attributed to the innermost
    benchmark host span open at each gap's midpoint, summed per name and
    sorted longest first."""
    busy = union(clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = [h for h in host if h.name != "bench.window"]
    totals: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [h for h in host if h.start <= mid < h.end]
        name = min(open_, key=lambda h: h.dur).name if open_ else "none"
        totals[name] = totals.get(name, 0.0) + (b - a)
    return sorted(totals.items(), key=lambda kv: -kv[1])


_SUFFIX = re.compile(r"[.:]\d+$")


def top_ops(ops: Iterable[Span], n: int = 10) -> List[Tuple[str, float]]:
    """Device time per op name (numeric suffixes folded), longest first."""
    totals: Dict[str, float] = {}
    for o in ops:
        k = _SUFFIX.sub("", o.name)
        totals[k] = totals.get(k, 0.0) + o.dur
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def scope_seconds(ops: Iterable[Span], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds per scope inside [lo, hi] (``""``: unscoped), each
    instant charged to the innermost op running then, the one that began
    last: a ``while`` op's event encloses its body's ops, whose time is
    not charged to it again.  The values sum to ``busy_seconds``."""
    ops = sorted(clip(ops, lo, hi), key=lambda o: (o.start, -o.end))
    totals: Dict[str, float] = {}
    heap: List[Tuple[float, float, int, str]] = []   # innermost on top
    i, t = 0, lo
    while i < len(ops) or heap:
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if not heap:
            if i == len(ops):
                break
            t = max(t, ops[i].start)
        while i < len(ops) and ops[i].start <= t:
            o = ops[i]
            heapq.heappush(heap, (-o.start, o.end, i, o.scope))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if not heap:
            continue
        nxt = ops[i].start if i < len(ops) else hi
        end = min(heap[0][1], nxt)
        scope = heap[0][3]
        totals[scope] = totals.get(scope, 0.0) + (end - t)
        t = end
    return totals


def scope_share(planes: Iterable[List[Span]], scope: str, lo: float,
                hi: float) -> Optional[float]:
    """Percent of busy device time inside [lo, hi] in which an op of
    ``scope`` was the innermost op running, over all planes; ``None``
    where no op of the scope ran."""
    busy = part = 0.0
    for ops in planes:
        busy += busy_seconds(ops, lo, hi)
        part += scope_seconds(ops, lo, hi).get(scope, 0.0)
    if busy <= 0 or part <= 0:
        return None
    return 100.0 * part / busy
