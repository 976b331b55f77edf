"""Roofline-term extraction from compiled dry-run artifacts.

* ``collective_bytes(hlo_text)`` — parse post-optimization HLO and sum the
  result-shape bytes of every all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute (cost_analysis does not report these).
* ``roofline_terms(...)`` — the three §Roofline terms in seconds, per
  chip, on the peaks of the named device (``peaks(device_kind)``).

``compiled.cost_analysis()`` / ``memory_analysis()`` describe the
PER-DEVICE partitioned program, so terms are computed per chip directly:
compute = flops/chip / peak, memory = bytes/chip / bw, collective =
coll_bytes/chip / link_bw.
"""

from __future__ import annotations

import re
import warnings
from typing import Dict, Optional

__all__ = ["collective_bytes", "roofline_terms", "PEAKS", "V5E", "peaks",
           "parse_shape_bytes", "sharded_stage_traffic"]

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip ICI).  ``ici_bw`` is the usable
# bytes/s of ONE link that the collective models charge (~50 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,     # bf16 per chip
        "hbm_bw": 819e9,          # bytes/s
        "ici_bw": 50e9,           # bytes/s/link (~ per-chip usable)
    },
}
V5E = "TPU v5 lite"     # device_kind JAX reports for a TPU v5e chip


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
# result shapes sit between "= " and " <opname>("
_LINE_RE = re.compile(
    r"=\s+(.*?)\s+(" + "|".join(_COLL_OPS) + r")(?:-start|-done)?\(")


def parse_shape_bytes(shape_str: str) -> int:
    """Sum bytes over every dtype[dims] occurrence in shape_str (handles
    tuple results)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind result bytes; '-done' twins of async pairs are skipped
    so started collectives are counted once."""
    out = {k: 0 for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue
        kind = m.group(2)
        out[kind] += parse_shape_bytes(m.group(1))
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


def sharded_stage_traffic(n_local: int, batch_rows: int, steps,
                          dtype_bytes: int = 4, *,
                          hw: dict,
                          use_diag: bool = False,
                          use_bias: bool = False,
                          in_width: Optional[int] = None,
                          out_width: Optional[int] = None,
                          fold_boundaries: bool = True,
                          overlap: bool = False,
                          n_row_blocks: Optional[int] = None) -> Dict:
    """Modeled per-chip traffic of a feature-sharded SPM schedule.

    ``steps`` is ``parallel.spm_shard.plan_steps(...)`` output: per
    ``("cross", ell, k)`` stage one collective-permute moves the chip's
    whole ``(batch_rows, n_local)`` slab to its XOR partner; per
    ``("local", off, strides)`` run the fused kernel costs one HBM read +
    one write of the slab (interior run boundaries of a multi-run plan are
    not modeled here — n_local is tile-sized in practice).

    Boundary terms: with ``fold_boundaries=True`` (the executor since the
    kernel-native-boundaries PR) the diag multiplies / bias add ride the
    schedule's boundary steps and a rectangular input is window-read
    straight from the (rows, in_width) operand.  ``ShardPlan.fold_din``
    and the windowed read still require the FIRST step local (a
    cross-starting schedule keeps the explicit d_in elementwise op and
    the gather-fallback window build, charged here), but the OUTPUT side
    folds on every schedule shape: a local ending absorbs d_out/bias into
    its last kernel run, and a cross ending folds them into the 2x2 mix
    epilogue itself (two O(n_local) vector operands applied on the store,
    d_out scaling the mixed result AFTER the add — no batch-wide
    elementwise op, no extra slab round-trip), so the model charges NO
    output-boundary bytes.  The
    always-paid remainder is the single local slice cutting the assembled
    output to ``out_width`` (one slab-portion read + write).
    ``fold_boundaries=False`` reproduces the PRE-fold executor for
    comparison: every enabled diag/bias term is one extra elementwise
    round-trip of the slab regardless of boundary kinds, and rectangular
    widths cost an XLA pad (write the slab from the narrower input) and
    slice (read the slab, write the narrower output) around the square
    core.  The overhead is reported per chip in
    ``boundary_bytes_per_chip`` and included in ``hbm_bytes_per_chip`` /
    ``memory_s``.

    Exposed vs hidden communication: with ``overlap=False`` (the
    step-serial executor) every cross stage's exchange is fully exposed —
    the whole slab must finish its local kernel run before a byte moves,
    and the 2x2 mix waits on the whole-slab permute.  With
    ``overlap=True`` the executor pipelines ``n_row_blocks`` row blocks
    (default: the executor's ``core.eligibility.OVERLAP_ROW_BLOCKS``)
    through the schedule, and a stage's per-block exchange
    hides under (a) OTHER cross stages' exchanges — each XOR distance
    ``k`` pairs over a distinct ICI link class, so stage ``k=2``'s block
    ``i`` flies while stage ``k=1``'s block ``i+1`` flies — and (b) the
    adjacent local compute (HBM-bound kernel time converted to
    ICI-equivalent bytes).  The exposed remainder is the busiest link
    class (less what compute hides, floored at its one-block pipeline
    fill) plus the other links' fill terms:

        exposed = max(bottleneck - compute_hide, bottleneck / nb)
                  + (total - bottleneck) / nb

    clamped to ``[0, total]``; ``hidden = total - exposed``.  The last
    block of each stage has nothing behind it to hide under, which is the
    ``(nb-1)/nb`` factor on the compute-hide term.

    Returns per-stage rows plus totals and roofline seconds on the
    peaks of ``hw`` (a ``peaks(device_kind)`` row: per-chip HBM vs ICI),
    so kernel_bench / dryrun can place the collective term next to the
    HBM term.
    """
    if overlap and n_row_blocks is None:
        # the executor's pipeline depth — shared constant, so the model
        # can never drift from the executed schedule.  (Tiny slabs that
        # degenerate to fewer blocks should pass the plan's actual count.)
        from repro.core.eligibility import OVERLAP_ROW_BLOCKS
        n_row_blocks = OVERLAP_ROW_BLOCKS
    nb = n_row_blocks if overlap else 1
    slab = batch_rows * n_local * dtype_bytes
    stages = []
    link_bytes: Dict[int, int] = {}
    coll_total = hbm_total = 0
    for step in steps:
        if step[0] == "cross":
            stages.append({"kind": "cross", "stage": step[1], "k": step[2],
                           "permute_bytes": slab, "hbm_bytes": 2 * slab})
            link_bytes[step[2]] = link_bytes.get(step[2], 0) + slab
            coll_total += slab
            hbm_total += 2 * slab
        else:
            stages.append({"kind": "local", "stage": step[1],
                           "n_stages": len(step[2]), "permute_bytes": 0,
                           "hbm_bytes": 2 * slab})
            hbm_total += 2 * slab
    if nb <= 1 or not link_bytes:
        exposed = coll_total
    else:
        # hbm_total here is still the bare stage traffic (the boundary
        # terms are added below, after the exposure split)
        bottleneck = max(link_bytes.values())
        compute_hide = (hbm_total / hw["hbm_bw"]) * hw["ici_bw"] \
            * (nb - 1) / nb
        exposed = (max(bottleneck - compute_hide, bottleneck / nb)
                   + (coll_total - bottleneck) / nb)
        exposed = min(max(exposed, 0.0), coll_total)
    exposed = int(round(exposed))
    # pro-rate per stage; the last cross row absorbs the rounding
    # remainder so the stage rows always sum to the per-chip total
    crosses = [row for row in stages if row["kind"] == "cross"]
    shared = 0
    for row in crosses:
        row["exposed_bytes"] = int(round(
            exposed * row["permute_bytes"] / coll_total))
        shared += row["exposed_bytes"]
    if crosses:
        crosses[-1]["exposed_bytes"] += exposed - shared
    boundary = 0
    first_local = bool(steps) and steps[0][0] == "local"
    if fold_boundaries:
        if use_diag and not first_local:
            boundary += 2 * slab               # explicit d_in elementwise
        # d_out/bias fold on EVERY schedule shape: into the last kernel
        # run on a local ending, into the mix epilogue's role vectors on
        # a cross ending (O(n_local) vector cost — not modeled as slab
        # traffic)
        if in_width is not None and not first_local:
            # gather-fallback window build instead of the in-kernel read
            boundary += slab + batch_rows * min(n_local, in_width) \
                * dtype_bytes
        if out_width is not None:
            # the lone always-paid boundary op: the local per-shard slice
            # of the assembled output (read + write of the kept portion)
            boundary += 2 * min(slab, batch_rows * out_width * dtype_bytes)
    else:
        n_elementwise = (2 if use_diag else 0) + (1 if use_bias else 0)
        boundary += n_elementwise * 2 * slab
        if in_width is not None:
            boundary += slab + batch_rows * min(n_local, in_width) \
                * dtype_bytes                       # pad: read d_in, write n
        if out_width is not None:
            boundary += slab + batch_rows * min(n_local, out_width) \
                * dtype_bytes                       # slice: read n, write out
    hbm_total += boundary
    return {"stages": stages,
            "overlap": bool(overlap),
            "n_row_blocks": nb,
            "permute_bytes_per_chip": coll_total,
            "exposed_permute_bytes_per_chip": exposed,
            "hidden_permute_bytes_per_chip": coll_total - exposed,
            "boundary_bytes_per_chip": boundary,
            "hbm_bytes_per_chip": hbm_total,
            "collective_s": coll_total / hw["ici_bw"],
            "exposed_collective_s": exposed / hw["ici_bw"],
            "memory_s": hbm_total / hw["hbm_bw"]}


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float,
                   hw: dict) -> Dict[str, float]:
    """The three per-chip roofline terms in seconds on the peaks ``hw``
    (a ``peaks(device_kind)`` row), the dominant one, and the compute
    share of the bound."""
    t_c = flops_per_chip / hw["peak_flops"]
    t_m = bytes_per_chip / hw["hbm_bw"]
    t_x = coll_bytes_per_chip / hw["ici_bw"]
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    bound = max(t_c, t_m, t_x)
    terms["roofline_fraction"] = (t_c / bound) if bound > 0 else 0.0
    return terms


def cost_analysis_terms(compiled) -> Dict[str, float]:
    """Pull flops / bytes-accessed from compiled.cost_analysis(), tolerant
    of backend differences (dict vs list-of-dicts)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes_accessed": byt, "raw_keys": len(ca)}


def memory_analysis_terms(compiled) -> Dict[str, float]:
    """Per-device memory-footprint terms from ``compiled.memory_analysis()``.

    Backends without the analysis raise ``NotImplementedError`` (or an
    ``XlaRuntimeError``, a ``RuntimeError`` subclass) — those degrade to
    ``{}`` WITH a warning so a traffic-model hole is visible instead of
    silently dropping the columns; anything else (a genuine bug) raises.
    """
    try:
        ma = compiled.memory_analysis()
    except (NotImplementedError, RuntimeError) as e:
        warnings.warn(
            f"memory_analysis unavailable on this backend "
            f"({type(e).__name__}: {e}); footprint terms omitted",
            RuntimeWarning, stacklevel=2)
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out
