"""Public entry for the fused SPM operator kernel.

``spm_stack_fused(x, coeffs, strides, d_in=..., d_out=..., bias=...)``
applies the paper's COMPLETE operator

    y = D_out * (B_L ... B_1) * D_in * x + bias

to the last axis of ``x`` with:

  * **run planning** — the stride schedule is split into maximal consecutive
    *runs* such that every stride in a run keeps its pairs inside one feature
    tile (``n_tile % (2*s) == 0``).  Each run is one ``pallas_call`` that
    fuses all its stages in VMEM (DESIGN.md §3.2); run boundaries are the
    only HBM round-trips.
  * **boundary folding** — ``d_in`` is folded into the FIRST run and
    ``d_out``/``bias`` into the LAST run of the plan, so the diagonal
    multiplies and the bias add cost zero extra HBM round-trips: the full
    operator is 1 read + 1 write of the activation per run (a single
    round-trip total for schedules that plan to one run) instead of the
    L+4 round-trips of the per-stage composition with unfused diag/bias.
  * **custom_vjp over the full operator** — backward uses the fused backward
    kernel per run (paper §4 closed forms, recomputing stage inputs in
    VMEM); the boundary runs additionally emit the closed-form diag/bias
    grads (g_dout = sum gy*z_L, g_bias = sum gy, g_din = sum delta_0*x), so
    training gets the same one-read-one-write property as the forward.
    The rotation variant's ``theta -> (a, b, c, d)`` chain stays OUTSIDE the
    kernel: it is O(nL), not activation-sized, and plain autodiff composes
    with the coefficient cotangent this VJP returns.
  * **batch/tile padding** — leading dims are flattened; rows are padded to
    the row-block so arbitrary batch sizes work (padded rows carry zero
    cotangents, so the batch-summed parameter grads are unaffected).
  * **rectangular-native boundaries** — ``in_width`` / ``out_width`` declare
    the true I/O widths of a rectangular linear (d_in -> d_out around the
    square n-wide operator).  The FIRST run of the plan reads only the
    (…, in_width) input and zero-fills to n in VMEM (iota mask, no XLA
    ``jnp.pad``); the LAST run computes and stores only the ``out_width``
    output columns (shrunk forward grid + masked partial-tile store).  The
    custom_vjp hands the input cotangent back as (…, in_width), and the
    masked loads make padded lanes contribute exact zeros to the
    coefficient/diag/bias grads.  Interior intermediates stay n-wide.
  * **dead-tile-free backward** — the backward grid of the last run visits
    only ``ceil(out_width / n_tile)`` feature tiles (tiles fully past
    ``out_width`` have an all-zero masked cotangent, so every grad they
    produce is an exact zero); skipped parameter-grad / g_x blocks are
    zero-initialized via ``input_output_aliases``, and the resulting
    exactly-zero g_x tail lets every upstream run of a multi-run plan
    prune the same dead tiles (``dead_from``).
  * **bf16 I/O** — activations may be bf16; in-VMEM compute is f32 and all
    parameter grads are returned f32 (cast back to the param dtype here).

Off-TPU the kernels run with ``interpret=True``; on TPU they compile
through Mosaic (``tests/test_tpu_compile.py`` compiles the main path's
kernels for a described v5e; ``chip_smoke.py`` runs them on the chip).
``kernels/ref.py`` is the oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.eligibility import (block_fusion_eligible,
                                    quant_acts_eligible, tiny_row_call)
from repro.kernels import spm_stack as K
from repro.kernels import quant as Q

__all__ = ["spm_stack_fused", "spm_stack_fused_q8", "spm_block_fused",
           "plan_runs", "plan_runs_for_rows", "tile_cap_for_rows",
           "pick_block_rows_for_plan", "default_interpret"]

MAX_TILE = 2048  # lane-dim tile cap: 16 VREG lanes x 128; VMEM-comfortable


def default_interpret() -> bool:
    """Whether pallas_call should run in interpret mode: True off-TPU
    (CPU/GPU validation), False on TPU (Mosaic compile)."""
    return jax.default_backend() != "tpu"


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


@functools.lru_cache(maxsize=None)
def plan_runs(n: int, strides: Tuple[int, ...],
              max_tile: int = MAX_TILE) -> Tuple[Tuple[Tuple[int, ...], int], ...]:
    """Split ``strides`` into runs of (strides, n_tile).

    Every stride s in a run satisfies ``n_tile % (2*s) == 0`` and
    ``n % n_tile == 0``.  Greedy: extend the current run while the lcm of
    pair spans stays within ``max_tile``; the tile is the largest multiple
    of that lcm that divides n and is <= max_tile (>= lcm always exists
    because the lcm of divisors of n divides n).
    """
    for s in strides:
        if n % (2 * s) != 0:
            raise ValueError(f"stride {s} invalid for n={n}")
    runs = []
    cur: list = []
    cur_lcm = 1

    def close():
        nonlocal cur, cur_lcm
        if not cur:
            return
        # largest multiple of cur_lcm dividing n, capped at max_tile
        tile = cur_lcm
        k = 1
        while True:
            cand = cur_lcm * (k + 1)
            if cand > max_tile or n % cand != 0:
                break
            k += 1
            tile = cand
        runs.append((tuple(cur), tile))
        cur, cur_lcm = [], 1

    for s in strides:
        span = 2 * s
        new_lcm = _lcm(cur_lcm, span)
        if cur and new_lcm > max_tile:
            close()
            new_lcm = span
        cur.append(s)
        cur_lcm = new_lcm
    close()
    return tuple(runs)


def tile_cap_for_rows(n: int, strides: Tuple[int, ...], n_rows: int,
                      dtype_bytes: int = 4) -> int:
    """Feature-tile cap for a call with ``n_rows`` flattened batch rows:
    the default ``MAX_TILE`` for training-sized calls, the widened
    ``spm_stack.pick_max_tile`` cap for tiny-row (decode) calls — see
    ``core/eligibility.tiny_row_call``."""
    if tiny_row_call(n_rows):
        return max(MAX_TILE, K.pick_max_tile(n, len(strides), dtype_bytes))
    return MAX_TILE


def plan_runs_for_rows(n: int, strides: Tuple[int, ...], n_rows: int,
                       dtype_bytes: int = 4
                       ) -> Tuple[Tuple[Tuple[int, ...], int], ...]:
    """Row-count-aware run plan: ``plan_runs`` under the tile cap
    ``tile_cap_for_rows`` picks for ``n_rows``.  The ONE planner both the
    executor (``spm_stack_fused``) and the compile-contract checker
    (``analysis/contracts.Artifacts.runs``) call, so the proven
    pallas-call count can never drift from the executed plan."""
    strides = tuple(int(s) for s in strides)
    return plan_runs(n, strides,
                     tile_cap_for_rows(n, strides, n_rows, dtype_bytes))


def _flatten_rows(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    return x.reshape(rows, x.shape[-1]), lead


def _pad_rows(x2: jax.Array, block_rows: int) -> Tuple[jax.Array, int]:
    rows = x2.shape[0]
    padded = -(-rows // block_rows) * block_rows
    if padded != rows:
        # spmlint: allow[SPM002] row padding to the kernel row block
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))
    return x2, rows


def pick_block_rows_for_plan(runs, n_rows: int, dtype_bytes: int, *,
                             overlap_bufs: bool = False,
                             block_bufs: bool = False) -> int:
    """One uniform row-block for every run of a plan (uniform row padding),
    budgeted per run: run r only keeps its OWN L_r + 2 tiles of its OWN
    width resident, so the binding constraint is the min over runs — not
    the old uniform (max_tile, total L) worst case, which under-sized the
    row block for every multi-run plan.  ``overlap_bufs`` additionally
    reserves the overlap (RDMA) kernels' per-block send/recv double
    buffers in the same budget (``spm_stack.overlap_vmem_bytes``) — set by
    the sharded executor whenever the in-kernel transport may engage, so
    a row block never outgrows VMEM once the comm slots move in.
    ``block_bufs`` budgets for the residual-BLOCK kernels instead
    (``spm_stack.block_vmem_bytes``): the norm-stat, activation, and
    residual buffers the block kernel keeps live on top of the per-run
    working set.  For the block entry, pass ONE pseudo-run holding both
    stacks' strides at the full width n — the block kernel never re-tiles
    between the stacks, so its binding run is the whole chain."""
    br = min(K.pick_block_rows(n_tile, len(run_strides),
                               dtype_bytes=dtype_bytes,
                               overlap=overlap_bufs, block=block_bufs)
             for run_strides, n_tile in runs)
    return min(br, max(8, 1 << (n_rows - 1).bit_length()))


# ---------------------------------------------------------------------------
# full-operator custom_vjp core
# ---------------------------------------------------------------------------
#
# Diff args: (x2, coeffs, d_in, d_out, bias).  The diag/bias operands are
# ALWAYS arrays (size-1 placeholders when absent) so the vjp signature is
# uniform; the static ``flags = (has_din, has_dout, has_bias, quant_acts,
# quant_coeffs)`` tuple decides which are real and whether the run chain
# moves int8 activations / coefficient tables (kernels/quant.py scale
# conventions).  Placeholders never reach a kernel and get zero grads.
#
# Quantized-activation chain (``quant_acts``; requires a uniform-tile plan,
# ``core/eligibility.quant_acts_eligible``): the input is quantized ONCE in
# XLA at entry, every run reads int8 + per-block scales and requantizes on
# its epilogue store (the scale array chains straight into the next run's
# x_scale), and the final int8 output is dequantized at exit.  The saved
# residuals are the int8 stage inputs + scales, so the backward's in-VMEM
# remat replays exactly the activations the quantized forward produced —
# the VJP is the true gradient of the quantized network (straight-through
# w.r.t. the entry quantization).
#
# Quantized coefficients (``quant_coeffs``): the f32 table is quantized
# per-stage here (O(nL), not activation-sized) and the kernels dequantize
# one stage at a time in VMEM.  The backward recomputes the SAME
# deterministic quantization from the saved f32 table, so its coefficient
# grads are bitwise what a pre-dequantized f32 table would produce, and
# the cotangent flows to the original f32 coeffs straight-through.

def _run_offsets(runs):
    offs, off = [], 0
    for run_strides, _ in runs:
        offs.append(off)
        off += len(run_strides)
    return offs


def _boundary_kw(r: int, n_runs: int, flags, d_in, d_out, bias) -> dict:
    """Kernel operands folded into run r: d_in on the first, d_out/bias on
    the last (both on a single-run plan)."""
    has_din, has_dout, has_bias = flags[:3]
    kw = {}
    if r == 0 and has_din:
        kw["d_in"] = d_in
    if r == n_runs - 1:
        if has_dout:
            kw["d_out"] = d_out
        if has_bias:
            kw["bias"] = bias
    return kw


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _fused_core(x2, coeffs, d_in, d_out, bias,
                strides, flags, block_rows, interpret, in_width, out_width,
                max_tile=MAX_TILE):
    """x2: (B, in_width or n) row-major; coeffs: (L, n//2, 4);
    d_in/d_out/bias: (n,).  Returns (B, out_width or n).  ``max_tile`` is
    the static feature-tile cap the run plan was made under (widened for
    tiny-row decode calls)."""
    return _fused_fwd(x2, coeffs, d_in, d_out, bias,
                      strides, flags, block_rows, interpret,
                      in_width, out_width, max_tile)[0]


def _fused_fwd(x2, coeffs, d_in, d_out, bias,
               strides, flags, block_rows, interpret, in_width, out_width,
               max_tile=MAX_TILE):
    n = 2 * coeffs.shape[1]
    runs = plan_runs(n, strides, max_tile)
    quant_acts = len(flags) > 3 and flags[3]
    quant_cf = len(flags) > 4 and flags[4]
    kcf, scf = (Q.quantize_coeffs(coeffs) if quant_cf else (coeffs, None))
    zs = []
    z, zscale = x2, None
    if quant_acts:
        z, zscale = Q.quantize_blocks(x2, block_rows, runs[0][1])
    off = 0
    for r, (run_strides, n_tile) in enumerate(runs):
        zs.append((z, zscale) if quant_acts else z)
        nL = len(run_strides)
        out = K.spm_stack_kernel_call(
            z, kcf[off: off + nL], strides=run_strides,
            block_rows=block_rows, n_tile=n_tile, interpret=interpret,
            in_width=in_width if r == 0 else None,
            out_width=out_width if r == len(runs) - 1 else None,
            x_scale=zscale,
            coeff_scale=scf[off: off + nL] if quant_cf else None,
            quant_out=quant_acts,
            **_boundary_kw(r, len(runs), flags, d_in, d_out, bias))
        z, zscale = out if quant_acts else (out, None)
        off += nL
    if quant_acts:
        # dequantize the final int8 output at exit — callers that want the
        # int8 payload itself use the forward-only spm_stack_fused_q8
        z = Q.dequantize_blocks(z, zscale, block_rows, runs[-1][1],
                                dtype=x2.dtype)
    return z, (tuple(zs), coeffs, d_in, d_out, bias)


def _fused_bwd(strides, flags, block_rows, interpret, in_width, out_width,
               max_tile, res, gy):
    zs, coeffs, d_in, d_out, bias = res
    has_din, has_dout, has_bias = flags[:3]
    quant_acts = len(flags) > 3 and flags[3]
    quant_cf = len(flags) > 4 and flags[4]
    # requantize the saved f32 table — deterministic, so the kernels see
    # bitwise the same dequantized values the forward used
    kcf, scf = (Q.quantize_coeffs(coeffs) if quant_cf else (coeffs, None))
    n = 2 * coeffs.shape[1]
    runs = plan_runs(n, strides, max_tile)
    offsets = _run_offsets(runs)
    delta = gy
    g_cf_parts = [None] * len(runs)
    g_din = g_dout = g_bias = None
    # Dead-tile chain: each run's backward visits only the feature tiles
    # holding live cotangent columns and returns a g_x that is EXACTLY
    # zero from its first skipped column on (zero-initialized unvisited
    # blocks), so the upstream run can prune its own grid to match (its
    # dead tiles' grads are all exact zeros for the same
    # tile-local-pairing reason).  The boundary must be re-derived from
    # EACH run's tile width: a run re-tiles the dead region to its own
    # n_tile, and a larger-tile run spreads live cotangent across its
    # whole edge tile (run tiles are not monotone across a plan).
    dead = None     # first all-zero column of the downstream run's g_x
    for r in range(len(runs) - 1, -1, -1):
        run_strides, n_tile = runs[r]
        lo = offsets[r]
        cf = kcf[lo: lo + len(run_strides)]
        z_r, zscale_r = zs[r] if quant_acts else (zs[r], None)
        last = r == len(runs) - 1
        out = K.spm_stack_bwd_kernel_call(
            z_r, cf, delta,
            d_in=d_in if (r == 0 and has_din) else None,
            d_out=d_out if (last and has_dout) else None,
            x_scale=zscale_r,
            coeff_scale=scf[lo: lo + len(run_strides)] if quant_cf
            else None,
            strides=run_strides, block_rows=block_rows, n_tile=n_tile,
            has_bias=last and has_bias,
            in_width=in_width if r == 0 else None,
            out_width=out_width if last else None,
            dead_from=None if last else dead,
            interpret=interpret)
        live = out_width if last else dead
        if live is not None and -(-live // n_tile) * n_tile < n:
            dead = -(-live // n_tile) * n_tile
        else:
            dead = None
        delta, gcf = out[0], out[1]
        vec = list(out[2:])
        if r == 0 and has_din:
            g_din = vec.pop(0)
        if last and has_dout:
            g_dout = vec.pop(0)
        if last and has_bias:
            g_bias = vec.pop(0)
        g_cf_parts[r] = gcf
    g_coeffs = jnp.concatenate(g_cf_parts, axis=0).astype(coeffs.dtype)
    if in_width is not None and delta.shape[-1] != in_width:
        # the kernel widened g_x to n (narrow output blocks would alias
        # clamped out-of-bounds stores — see spm_stack_bwd_kernel_call);
        # hand the custom_vjp its contract shape back
        delta = delta[:, :in_width]

    def _vg(g, like):
        if g is None:
            return jnp.zeros_like(like)
        return g.astype(like.dtype)

    return (delta, g_coeffs, _vg(g_din, d_in), _vg(g_dout, d_out),
            _vg(g_bias, bias))


_fused_core.defvjp(_fused_fwd, _fused_bwd)


def spm_stack_fused(x: jax.Array, coeffs: jax.Array,
                    strides: Sequence[int], *,
                    d_in: Optional[jax.Array] = None,
                    d_out: Optional[jax.Array] = None,
                    bias: Optional[jax.Array] = None,
                    in_width: Optional[int] = None,
                    out_width: Optional[int] = None,
                    block_rows: int | None = None,
                    quant_acts: bool = False,
                    quant_coeffs: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """Fused SPM operator over the last axis of ``x``.

    x: (..., in_width or n) with n = 2 * coeffs.shape[1] divisible by 2*s
    for every stride; coeffs (L, n//2, 4); optional d_in/d_out/bias: (n,)
    folded into the boundary runs.  ``in_width`` / ``out_width`` (each
    <= n) make the operator rectangular-native: the input is zero-filled
    to n inside the first run and only ``out_width`` output columns are
    computed/stored by the last, with the input cotangent returned as
    (..., in_width).  Differentiable in x, coeffs, and the diag/bias
    operands (closed-form VJP); with everything optional omitted this is
    exactly the bare square stage stack (back-compat entry).

    ``quant_acts`` moves the run chain's HBM activation traffic at int8
    with per-(row-block, feature-tile) scales (quantize at entry,
    dequantize-in-VMEM / requantize-on-store per run, dequantize at
    exit); requires a uniform-tile run plan
    (``core/eligibility.quant_acts_eligible`` — falls back to f32 I/O
    gracefully otherwise).  ``quant_coeffs`` moves the coefficient table
    at int8 with per-stage scales dequantized in VMEM; coefficient grads
    stay f32 and bitwise-comparable to a pre-dequantized f32 table.  Both
    knobs change only BYTES MOVED, never the in-VMEM f32 compute.
    """
    strides = tuple(int(s) for s in strides)
    n = 2 * coeffs.shape[1]
    if in_width == n:
        in_width = None
    if out_width == n:
        out_width = None
    for w, name in ((in_width, "in_width"), (out_width, "out_width")):
        if w is not None and not 0 < w <= n:
            raise ValueError(f"{name}={w} outside (0, {n}]")
    expect = in_width if in_width is not None else n
    if x.shape[-1] != expect:
        raise ValueError(f"expected (..., {expect}), got {x.shape}")
    if interpret is None:
        interpret = default_interpret()
    x2, lead = _flatten_rows(x)
    max_tile = tile_cap_for_rows(n, strides, x2.shape[0],
                                 dtype_bytes=x.dtype.itemsize)
    runs = plan_runs(n, strides, max_tile)
    if block_rows is None:
        block_rows = pick_block_rows_for_plan(
            runs, x2.shape[0], dtype_bytes=x.dtype.itemsize)
    x2p, rows = _pad_rows(x2, block_rows)
    flags = (d_in is not None, d_out is not None, bias is not None,
             quant_acts and quant_acts_eligible(runs), bool(quant_coeffs))
    placeholder = jnp.zeros((1,), x.dtype)
    y2 = _fused_core(
        x2p, coeffs,
        d_in if d_in is not None else placeholder,
        d_out if d_out is not None else placeholder,
        bias if bias is not None else placeholder,
        strides, flags, block_rows, interpret, in_width, out_width,
        max_tile)
    if y2.shape[0] != rows:       # row padding only; never a feature slice
        y2 = y2[:rows]
    out_w = out_width if out_width is not None else n
    return y2.reshape(lead + (out_w,))


def spm_stack_fused_q8(qx: jax.Array, x_scale: jax.Array,
                       coeffs: jax.Array, strides: Sequence[int], *,
                       d_in: Optional[jax.Array] = None,
                       d_out: Optional[jax.Array] = None,
                       bias: Optional[jax.Array] = None,
                       in_width: Optional[int] = None,
                       out_width: Optional[int] = None,
                       quant_coeffs: bool = True,
                       interpret: bool | None = None):
    """Int8-native fused forward: int8 in, int8 out (inference entry).

    ``qx``: (B, in_width or n) int8 rows already quantized per
    (row-block, feature-tile) (``kernels/quant.quantize_blocks``);
    ``x_scale``: its (B // block_rows, tiles) f32 scale array —
    ``block_rows`` is derived from it, so the two must come from the same
    quantization.  Runs the whole run chain with int8 activation I/O
    (and, by default, an int8 per-stage-scaled coefficient table) and
    returns ``(qy int8 (B, out_width or n), y_scale)`` WITHOUT
    dequantizing: end to end, HBM sees no f32 activation bytes — the
    property the quant compile contract checks on this entry.  Forward
    only (no custom_vjp); training uses ``spm_stack_fused(...,
    quant_acts=True)``, which shares the same run chain but
    quantizes/dequantizes at the jit boundary.  Raises when the run plan
    is not uniform-tile (``core/eligibility.quant_acts_eligible``).
    """
    strides = tuple(int(s) for s in strides)
    n = 2 * coeffs.shape[1]
    if in_width == n:
        in_width = None
    if out_width == n:
        out_width = None
    assert qx.dtype == jnp.int8, qx.dtype
    B = qx.shape[0]
    if B % x_scale.shape[0]:
        raise ValueError(f"rows {B} not a multiple of scale rows "
                         f"{x_scale.shape[0]}")
    block_rows = B // x_scale.shape[0]
    max_tile = tile_cap_for_rows(n, strides, B, dtype_bytes=1)
    runs = plan_runs(n, strides, max_tile)
    if not quant_acts_eligible(runs):
        raise ValueError(f"run plan {runs} is not uniform-tile; int8 "
                         "activation I/O cannot chain across its runs")
    if interpret is None:
        interpret = default_interpret()
    kcf, scf = (Q.quantize_coeffs(coeffs) if quant_coeffs
                else (coeffs, None))
    flags = (d_in is not None, d_out is not None, bias is not None)
    z, zscale = qx, x_scale
    off = 0
    for r, (run_strides, n_tile) in enumerate(runs):
        nL = len(run_strides)
        z, zscale = K.spm_stack_kernel_call(
            z, kcf[off: off + nL], strides=run_strides,
            block_rows=block_rows, n_tile=n_tile, interpret=interpret,
            in_width=in_width if r == 0 else None,
            out_width=out_width if r == len(runs) - 1 else None,
            x_scale=zscale,
            coeff_scale=scf[off: off + nL] if quant_coeffs else None,
            quant_out=True,
            **_boundary_kw(r, len(runs), flags, d_in, d_out, bias))
        off += nL
    return z, zscale


# ---------------------------------------------------------------------------
# residual-block (megakernel) custom_vjp core + public entry
# ---------------------------------------------------------------------------
#
# Diff args: (x2, gamma, cf1, din1, dout1, bias1, cf2, din2, dout2,
# bias2) — size-1 placeholders when absent, exactly the _fused_core
# convention.  The static tuple rides one nondiff slot: (strides1,
# strides2, activation, flags, block_rows, residual, widths, eps,
# interpret) with flags = (has_norm, has_bias1, has_stack2, has_bias2).
# The ONLY forward residuals beyond the operands are the (B, 1) row
# statistics — the backward kernel remats the normalized input, both
# stacks' stage inputs, and the mid activation in VMEM from (x, rstd).

def _block_args(gamma, cf1, din1, dout1, bias1, cf2, din2, dout2, bias2,
                statics):
    """Expand the placeholder convention into the kernel-call kwargs
    shared by the block forward and backward wrappers."""
    (strides1, strides2, activation, flags, block_rows, residual,
     in_width, mid_width, out_width, eps, interpret) = statics
    has_norm, has_bias1, has_stack2, has_bias2 = flags
    return dict(
        bias1=bias1 if has_bias1 else None,
        gamma=gamma if has_norm else None,
        coeffs2=cf2 if has_stack2 else None,
        d_in2=din2 if has_stack2 else None,
        d_out2=dout2 if has_stack2 else None,
        bias2=bias2 if (has_stack2 and has_bias2) else None,
        strides1=strides1,
        strides2=strides2 if has_stack2 else None,
        activation=activation, block_rows=block_rows, residual=residual,
        in_width=in_width, mid_width=mid_width, out_width=out_width,
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def _block_core(x2, gamma, cf1, din1, dout1, bias1,
                cf2, din2, dout2, bias2, statics):
    """x2: (B, in_width) row-major; gamma/diag/bias: (n,) (placeholders
    when the matching flag is off); cf1/cf2: (L, n//2, 4).  Returns
    (B, out_width)."""
    return _block_fwd(x2, gamma, cf1, din1, dout1, bias1,
                      cf2, din2, dout2, bias2, statics)[0]


def _block_fwd(x2, gamma, cf1, din1, dout1, bias1,
               cf2, din2, dout2, bias2, statics):
    kw = _block_args(gamma, cf1, din1, dout1, bias1,
                     cf2, din2, dout2, bias2, statics)
    out = K.spm_block_kernel_call(x2, cf1, din1, dout1, eps=statics[9],
                                  **kw)
    rstd = out[1] if kw["gamma"] is not None else None
    return out[0], (x2, rstd, gamma, cf1, din1, dout1, bias1,
                    cf2, din2, dout2, bias2)


def _block_bwd(statics, res, gy):
    (x2, rstd, gamma, cf1, din1, dout1, bias1,
     cf2, din2, dout2, bias2) = res
    flags = statics[3]
    has_norm, has_bias1, has_stack2, has_bias2 = flags
    kw = _block_args(gamma, cf1, din1, dout1, bias1,
                     cf2, din2, dout2, bias2, statics)
    kw.pop("interpret")
    out = list(K.spm_block_bwd_kernel_call(
        x2, gy, cf1, din1, dout1, rstd=rstd, interpret=statics[10], **kw))
    gx = out.pop(0)
    g_gamma = out.pop(0) if has_norm else None
    g_cf1, g_din1, g_dout1 = out.pop(0), out.pop(0), out.pop(0)
    g_bias1 = out.pop(0) if has_bias1 else None
    g_cf2 = g_din2 = g_dout2 = g_bias2 = None
    if has_stack2:
        g_cf2, g_din2, g_dout2 = out.pop(0), out.pop(0), out.pop(0)
        if has_bias2:
            g_bias2 = out.pop(0)

    def _g(g, like):
        if g is None:
            return jnp.zeros_like(like)
        return g.astype(like.dtype)

    return (gx, _g(g_gamma, gamma), g_cf1.astype(cf1.dtype),
            _g(g_din1, din1), _g(g_dout1, dout1), _g(g_bias1, bias1),
            _g(g_cf2, cf2), _g(g_din2, din2), _g(g_dout2, dout2),
            _g(g_bias2, bias2))


_block_core.defvjp(_block_fwd, _block_bwd)


def spm_block_fused(x: jax.Array, *,
                    coeffs1: jax.Array, d_in1: jax.Array,
                    d_out1: jax.Array, strides1: Sequence[int],
                    bias1: Optional[jax.Array] = None,
                    gamma: Optional[jax.Array] = None,
                    coeffs2: Optional[jax.Array] = None,
                    d_in2: Optional[jax.Array] = None,
                    d_out2: Optional[jax.Array] = None,
                    bias2: Optional[jax.Array] = None,
                    strides2: Optional[Sequence[int]] = None,
                    activation: Optional[str] = None,
                    residual: bool = False,
                    in_width: Optional[int] = None,
                    mid_width: Optional[int] = None,
                    out_width: Optional[int] = None,
                    eps: float = 1e-6,
                    block_rows: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Residual-block megakernel over the last axis of ``x``: ONE fused
    Pallas region lowering

        y = [x +] stack2(act(stack1(rms_norm(x))))

    where each stack is a complete SPM operator (d_in -> stages ->
    d_out [+ bias]) and every piece is optional — ``gamma=None`` skips
    the norm prologue, ``strides2=None`` ends after stack 1 (the
    norm-prologue-only fused-qkv entry), ``activation=None`` is the
    identity, ``residual`` adds x on the store (requires out_width ==
    in_width).

    ``gamma`` is the (in_width,) RMS scale (``eps`` matching
    ``layers/norms.rms_norm``); widths default to ``in_width =
    x.shape[-1]``, ``out_width = n``, and ``mid_width`` (the true width
    between the stacks — d_ff for an FFN) to ``n`` with a second stack,
    ``out_width`` without.  Both stacks must satisfy
    ``core/eligibility.block_fusion_eligible`` — single full-width run
    each, so the mid activation never leaves VMEM (raises otherwise; the
    layer entries resolve eligibility BEFORE calling this).
    Differentiable in every array operand: the closed-form custom_vjp
    saves only x and the (rows, 1) row statistics and remats the rest in
    VMEM (remat-from-row-stats).
    """
    strides1 = tuple(int(s) for s in strides1)
    strides2 = (tuple(int(s) for s in strides2)
                if strides2 is not None else None)
    n = 2 * coeffs1.shape[1]
    if not block_fusion_eligible(n, strides1, strides2, activation):
        raise ValueError(
            f"block fusion ineligible: n={n}, strides1={strides1}, "
            f"strides2={strides2}, activation={activation!r}")
    if in_width is None:
        in_width = x.shape[-1]
    if out_width is None:
        out_width = n
    if mid_width is None:
        mid_width = n if strides2 is not None else out_width
    for w, name in ((in_width, "in_width"), (mid_width, "mid_width"),
                    (out_width, "out_width")):
        if not 0 < w <= n:
            raise ValueError(f"{name}={w} outside (0, {n}]")
    if x.shape[-1] != in_width:
        raise ValueError(f"expected (..., {in_width}), got {x.shape}")
    if residual and out_width != in_width:
        raise ValueError(f"residual needs out_width == in_width, got "
                         f"{out_width} != {in_width}")
    if (strides2 is not None) != (coeffs2 is not None):
        raise ValueError("strides2 and coeffs2 must be given together")
    if interpret is None:
        interpret = default_interpret()
    if gamma is not None and gamma.shape[-1] != n:
        # zero-fill the RMS scale to operator width in O(n) (dead lanes
        # multiply exact zeros either way)
        gamma = jnp.zeros((n,), gamma.dtype).at[:in_width].set(gamma)
    x2, lead = _flatten_rows(x)
    if block_rows is None:
        # ONE pseudo-run with both stacks' strides at full width: the
        # block kernel never re-tiles, and block_bufs reserves the
        # norm/activation/residual buffers it keeps live
        runs = ((strides1 + (strides2 or ()), n),)
        block_rows = pick_block_rows_for_plan(
            runs, x2.shape[0], dtype_bytes=x.dtype.itemsize,
            block_bufs=True)
    x2p, rows = _pad_rows(x2, block_rows)
    flags = (gamma is not None, bias1 is not None, strides2 is not None,
             bias2 is not None)
    statics = (strides1, strides2, activation, flags, block_rows,
               residual, in_width, mid_width, out_width, eps,
               bool(interpret))
    ph = jnp.zeros((1,), x.dtype)
    y2 = _block_core(
        x2p,
        gamma if gamma is not None else ph,
        coeffs1, d_in1, d_out1,
        bias1 if bias1 is not None else ph,
        coeffs2 if coeffs2 is not None else ph,
        d_in2 if d_in2 is not None else ph,
        d_out2 if d_out2 is not None else ph,
        bias2 if bias2 is not None else ph,
        statics)
    if y2.shape[0] != rows:       # row padding only; never a feature slice
        y2 = y2[:rows]
    return y2.reshape(lead + (out_width,))
