"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/<t>/``.
``load`` turns it into plain ``Span`` lists: the device operations of each
accelerator plane (line "XLA Ops"), with the XLA module (jitted program)
each ran in, and the benchmark's own host spans (``TraceAnnotation``
names starting with ``bench.``).  Everything else here works on those
lists, so it is tested on synthetic spans without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float          # seconds, on the trace's common clock
    end: float
    module: str = ""      # XLA module of a device op ("" for host spans)

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


def _stat(ev, key: str) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def op_name(event_name: str) -> str:
    """An op's own name from its event's name, which on the TPU is the
    whole HLO instruction (``%fusion.3 = f32[8] fusion(%spm_call.2)``):
    its operands must not make it match a kernel's pattern."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str, cpu_stand_in: bool = False) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``.  A trace with
    no accelerator plane that holds device operations is an error, unless
    ``cpu_stand_in`` (tests on the CPU only) lets the XLA ops of the
    host's threads stand in."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    device_ops: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops: List[Span] = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append(Span(op_name(ev.name), ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9,
                                    _stat(ev, "hlo_module") or ""))
            modules = [Span(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                       for line in plane.lines if line.name == "XLA Modules"
                       for ev in line.events]
            if ops:   # a plane with no op is no device that ran (an
                # idle TPU plugin loaded beside the CPU backend has one)
                device_ops[plane.name] = _attach_modules(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Span(ev.name, ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns)
                                         * 1e-9))
    if not device_ops:
        if not cpu_stand_in:
            raise RuntimeError(f"the trace under {trace_dir} has no "
                               f"accelerator plane; its host ops are no "
                               f"device numbers")
        device_ops["/host:CPU"] = [
            Span(op_name(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9,
                 _stat(ev, "hlo_module") or "")
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if _stat(ev, "hlo_op") is not None]
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
