"""What every cell's run shares: the chip check, the compile counter, the
profiler capture with the benchmark's host spans, the reading of
per-layer metrics and the result line."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
import types
from typing import Dict, List, Optional

import checks as checks_mod
import peaks as peaks_mod
import spec
import trace_reduce as trace_mod
import work as work_mod

TRACE_ROOT = os.path.join(spec.ROOT, ".bench_traces")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def require_chips(n: int) -> int:
    """0 when JAX's devices are TPUs and at least ``n`` of them, else a
    non-zero exit code (and no result)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        log("no TPU: JAX's first device is not a TPU; no result")
        return 2
    if len(devs) < n:
        log(f"the cell needs {n} chips, JAX sees {len(devs)}; no result")
        return 2
    return 0


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``on`` (the measured window), with their names."""

    def __init__(self):
        import jax
        self.on = False
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.names.append(str(kw.get("fun_name", "?")))

    @property
    def count(self) -> int:
        return len(self.names)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Tracer:
    """One profiler capture over a stretch of the window."""

    def __init__(self, enabled: bool, tag: str, cpu_stand_in: bool = False):
        self.enabled = enabled
        self.cpu_stand_in = cpu_stand_in
        self.dir = os.path.join(TRACE_ROOT, tag)
        self.active = False
        self.done = False
        self.result: Optional[trace_mod.Trace] = None
        self._window = None

    def start(self):
        if not self.enabled or self.active or self.done:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.t_start = time.time()
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.active = True

    def stop(self):
        if not self.active:
            return
        import jax
        self._window.__exit__(None, None, None)
        self.t_stop = time.time()
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def load(self) -> Optional[trace_mod.Trace]:
        if not self.done:
            return None
        self.result = trace_mod.load(self.dir, self.cpu_stand_in)
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.result


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def read_per_layer(cell: spec.Cell, host: dict, tr: trace_mod.Trace,
                   kind: str) -> Dict[str, float]:
    lo, hi = tr.window()
    ctx = types.SimpleNamespace(
        kind=kind, shape=spec.published(cell), peaks=peaks_mod.peaks(
            host["device_kind"]),
        trace=tr, window=(lo, hi),
        ops=[trace_mod.clip(v, lo, hi) for v in tr.device_ops.values()],
        host=host, work=work_mod, tr=trace_mod)
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = v
        elif tr.stand_in:
            continue
        elif may_be_silent(m["name"]):
            log(f"per-layer metric {m['name']} read nothing: its kernel "
                f"did not run in the traced stretch; left out of the line")
        else:
            raise RuntimeError(f"per-layer metric {m['name']} read nothing "
                               f"from the traced stretch")
    return out


def may_be_silent(metric: str) -> bool:
    """A kernel's roofline (``<kernel>_roofline.<phase>``) reads nothing
    once a program takes that kernel off the path; every other per-layer
    metric reads on every traced run of its cells."""
    return metric.split(".", 1)[0].endswith("_roofline")


def stall_note(what: str, seconds: List[float]) -> str:
    """A line on the window's steadiness: the median and the longest of
    its steps or tick intervals, and where the longest fell."""
    if not seconds:
        return f"{what} seconds: none in the window"
    i = max(range(len(seconds)), key=seconds.__getitem__)
    med = sorted(seconds)[len(seconds) // 2]
    return (f"{what} seconds: median {med:.4f} longest {seconds[i]:.4f} "
            f"(number {i} of {len(seconds)}, {sum(seconds[:i]):.2f}s in)")


def trace_summary(tr: trace_mod.Trace) -> dict:
    lo, hi = tr.window()
    planes = list(tr.device_ops.values())
    busy = sum(trace_mod.busy_seconds(ops, lo, hi) for ops in planes) \
        / max(len(planes), 1)
    ops0 = trace_mod.clip(planes[0], lo, hi) if planes else []
    return {
        "busy_s": busy, "window_s": hi - lo,
        "breakdown": {
            "device_ops": [[k, v] for k, v in trace_mod.top_ops(ops0)],
            "idle_gaps": [[k, v] for k, v in trace_mod.idle_gaps(
                ops0, tr.host_spans, lo, hi)[:10]]}}


def run(cell: spec.Cell, args, t_start: float,
        faults: Optional[dict] = None, cpu_stand_in: bool = False) -> dict:
    """Drive the cell's kind and assemble the result line.  ``faults``
    plants a named fault in the timed path and ``cpu_stand_in`` lets the
    host's XLA ops stand in for a device trace (tests only)."""
    drv = spec.kind_module(cell)
    counter = CompileCounter()
    tracer = Tracer(bool(args.trace), f"{cell.name}-{args.seed}",
                    cpu_stand_in)
    out = drv.run(cell, args, t_start=t_start, counter=counter,
                  tracer=tracer, span=span, log=log, faults=faults or {})
    dev = out["device"]
    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": dev}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        tr = tracer.load()
        if tr is None:
            raise RuntimeError("the traced run took no trace")
        summ = trace_summary(tr)
        log(f"breakdown {json.dumps(summ['breakdown'])}")
        dev["busy_s"], dev["window_s"] = summ["busy_s"], summ["window_s"]
        host = dict(out["host"], device_kind=dev["kind"])
        vals = read_per_layer(cell, host, tr, cell.kind)
        result["breakdown"] = summ["breakdown"]
    else:
        vals = {m["name"]: out["e2e"][m["name"]] for m in cell.end_to_end}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in vals.items()}
    numbers = dict(out["numbers"])
    numbers["compiles_in_window"] = float(counter.count)
    if counter.count:
        log(f"compiled in the window: {counter.names}")
    limits = dict(cell.limits)
    limits.setdefault("compiles_in_window", {"limit": 0.0})
    ok, checks = checks_mod.judge(numbers, limits)
    result["correct"] = bool(ok)
    result["checks"] = checks
    for k, v in out.get("notes", {}).items():
        log(f"note {k} {v}")
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
