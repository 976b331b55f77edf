"""Fused L-stage SPM kernel (Pallas / TPU) — full-operator edition.

Why a kernel (DESIGN.md §3.2): SPM has arithmetic intensity ~O(L) FLOP/byte
(vs ~n/2 for a dense matmul), far below the TPU v5e balance point
(~240 FLOP/byte @ 197 TFLOP/s bf16 / 819 GB/s HBM), so SPM is memory-bound by
construction.  Lowering each stage separately costs L+1 HBM round-trips of
the full activation; this kernel keeps an activation tile resident in VMEM
and applies ALL stages before writing back — one read + one write.

Full-operator folding (this PR): the paper's complete operator is

    y = D_out * (B_L ... B_1) * D_in * x + bias

and with only the stage stack fused, the two diagonal multiplies and the
bias add each cost one more full-activation HBM round-trip around the
kernel.  Both kernels therefore take OPTIONAL ``d_in`` / ``d_out`` / ``bias``
tile refs ((1, n_tile) slabs riding the lane dimension): ``d_in`` is applied
in VMEM before the first stage of the FIRST run, ``d_out``/``bias`` after
the last stage of the LAST run (ops.py folds them into the boundary runs of
the run plan).  The backward kernel emits their closed-form grads next to
the eq. 12-14 coefficient grads:

    g_bias  = sum_batch gy                       (accumulated across row tiles)
    g_dout  = sum_batch gy * z_L                 (z_L recomputed in VMEM)
    g_din   = sum_batch delta_0 * x              (delta_0 = backprop through stages)
    g_x     = delta_0 * d_in

Activation I/O may be bf16; all in-VMEM compute is f32 (inputs are upcast on
load, outputs downcast on the final store), so the serve engine's bf16 path
gets the fused kernel without precision loss in the accumulations
(coefficient/diag/bias grads are always written f32).

Rectangular-native boundaries (this PR): SPM is defined on a square n-wide
operator, but the projection linears it replaces are rectangular
(d_in -> d_out with n = even_ceil(max)).  Instead of the caller zero-padding
the input and slicing the output in XLA (two extra full-activation HBM
round-trips + up to n - d_out dead columns of compute), both kernels take
static ``in_width`` / ``out_width``:

  * ``in_width``  — the input operand is (B, in_width); the kernel reads
    whatever the (block_rows, n_tile) BlockSpec delivers (blocks past the
    array edge are padding) and zero-fills lanes with virtual column index
    >= in_width via an iota mask IN VMEM, before the d_in fold.
  * ``out_width`` — the output operand is (B, out_width); the final store
    relies on Pallas' masked out-of-bounds store semantics for the partial
    edge tile, and the FORWARD grid visits only ceil(out_width / n_tile)
    feature tiles (columns past out_width are dead by construction: stages
    in one run pair lanes tile-locally, so discarded output tiles depend
    only on discarded input tiles).
  * The backward keeps the FULL feature grid: every gcf / diag / bias
    output block must be written (unvisited blocks would be garbage), and
    masked x / gy loads make padded lanes contribute exact zeros to the
    coefficient, diag, and bias grads while g_x comes back (B, in_width).

ops.py sets the widths only on the boundary runs of a multi-run plan; the
interior intermediates stay n-wide.

Dead-tile-free backward (this PR): a feature tile whose columns all sit at
or past ``out_width`` receives an all-zero gy after the in-VMEM mask, and
because stages inside one run pair lanes tile-locally, EVERY gradient the
tile produces (gcf, g_din, g_dout, g_bias, g_x) is exactly zero.  The
backward grid therefore visits only ``ceil(out_width / n_tile)`` feature
tiles; the parameter-grad (and, when wider than the visited region, g_x)
blocks of the skipped tiles are zero-initialized by aliasing pre-zeroed
operands onto the outputs (``input_output_aliases`` — unvisited blocks
keep their input value).  ``dead_from`` extends the same skip to the
earlier runs of a multi-run plan: the last run's cotangent is exactly zero
from its first skipped column on, so upstream runs prune the same tail.

Sharded windowed boundaries (this PR): inside the distributed executor
(``parallel/spm_shard.py``) shard ``j`` owns global columns
``[j*n_local, (j+1)*n_local)`` of a rectangular operator whose input is a
feature-complete ``(rows, in_width)`` array.  Both kernels take an optional
``col_base`` — a TRACED (1,) int32 scalar holding the shard's base feature
tile — delivered via Pallas scalar prefetch: the x (forward / backward) and
gy (backward) BlockSpec index maps offset their feature-block index by it,
so each shard reads its own window straight out of the replicated operand
(the padded square array is never materialized in HBM), and the iota masks
compare against the GLOBAL column ``(col_base + j) * n_tile + lane``.  With
``col_base`` the widths are global widths, the output stays the shard-local
``(rows, n_local)`` slab, and the backward keeps the full local grid (the
grid is SPMD-uniform across shards; a shard's dead edge tiles are hidden by
the fully-live interior shards that bound the step wall-clock anyway).

Layout notes (TPU-native adaptation of the paper's CPU loop):
  * The feature axis rides the 128-wide lane dimension; batch rides sublanes.
  * Lane-major stage mix: a stride-s stage is
    ``y = w_self * z + w_partner * z[partner]`` with two (1, n_tile) weight
    rows per stage (``lane_major`` lays the (L, n/2, 4) pair table out as a
    (2L, n) slab in XLA; ``pair_table`` maps the grads back).  The partner
    values are two lane rotations (``pltpu.roll`` by s and n_tile - s) and a
    select on the lane parity — VPU/XLU work with no relayout.  (A reshape
    to (bb, g, 2, s) is what interpret mode accepts and Mosaic refuses.)
  * Grid tiles: (batch_tile, feature_tile).  A feature tile of width n_t can
    fuse every stage with n_t % (2 s) == 0 (pair stays inside the tile);
    ops.py splits the schedule into maximal tile-local runs and composes.

Validated in interpret mode against kernels/ref.py;
tests/test_tpu_compile.py compiles the main path's kernels for a
described v5e, and chip_smoke.py runs them on the chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spm_stack_kernel_call", "spm_stack_bwd_kernel_call",
           "spm_overlap_kernel_call", "spm_overlap_bwd_kernel_call",
           "spm_block_kernel_call", "spm_block_bwd_kernel_call",
           "pick_block_rows", "vmem_bytes", "overlap_vmem_bytes",
           "block_vmem_bytes", "lane_major", "pair_table"]

_F32 = jnp.float32


def _mask_cols(z, tile_idx, width: int):
    """Zero lanes whose VIRTUAL column index (feature-tile offset + lane)
    is >= width — the in-VMEM realization of zero-padding a (B, width)
    operand up to the square operator width n."""
    nt = z.shape[-1]
    col = tile_idx * nt + jax.lax.broadcasted_iota(jnp.int32, z.shape,
                                                   z.ndim - 1)
    return jnp.where(col < width, z, 0.0)


def lane_major(coeffs: jax.Array, strides: Tuple[int, ...]) -> jax.Array:
    """(L, n//2, 4) pair table -> (2L, n) lane-major coefficient slab.

    Stage ``l`` pairs lane ``lo`` with ``hi = lo + s``; the pair's block
    ``[[a, b], [c, d]]`` becomes two per-lane weights so that the stage is
    ``y = w_self * z + w_partner * z[partner(lane)]``: row ``2l`` holds
    ``w_self`` (a on lo lanes, d on hi lanes), row ``2l + 1`` holds
    ``w_partner`` (b on lo lanes, c on hi lanes).  Pure layout (slices,
    reverse, transpose) in XLA, O(nL); the kernels never see the pair
    table, whose 4-wide minor axis would fight the 128-lane tiling."""
    n = 2 * coeffs.shape[1]
    rows = []
    for ell, s in enumerate(strides):
        t = coeffs[ell].reshape(n // (2 * s), s, 2, 2)      # [g, k, r, c]
        u = jnp.stack([t[:, :, 0, :], t[:, :, 1, ::-1]], axis=2)
        rows.append(jnp.transpose(u, (3, 0, 2, 1)).reshape(2, n))
    return jnp.concatenate(rows, axis=0)


def pair_table(slab: jax.Array, strides: Tuple[int, ...]) -> jax.Array:
    """Inverse of ``lane_major``: (2L, n) -> (L, n//2, 4).  Maps the
    kernels' lane-major coefficient grads back onto the parameter
    layout."""
    n = slab.shape[1]
    out = []
    for ell, s in enumerate(strides):
        w = slab[2 * ell: 2 * ell + 2].reshape(2, n // (2 * s), 2, s)
        u = jnp.transpose(w, (1, 3, 2, 0))                  # [g, k, r, w]
        t = jnp.stack([u[:, :, 0, :], u[:, :, 1, ::-1]], axis=2)
        out.append(t.reshape(n // 2, 4))
    return jnp.stack(out)


def _partner(z, s: int):
    """``z`` at each lane's stride-``s`` partner, on a resident (rows, nt)
    tile whose pairs are tile-local (``nt % (2s) == 0``): lanes with
    ``(lane // s) % 2 == 0`` read ``lane + s``, the others ``lane - s``.
    Two lane rotations and a select on the VPU/XLU — no relayout."""
    nt = z.shape[-1]
    ax = z.ndim - 1
    if 2 * s == nt:                     # both rotations coincide
        return pltpu.roll(z, s, ax)
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, ax)
    if s & (s - 1) == 0:
        lo = (lane & s) == 0
    else:
        lo = jax.lax.rem(lane, 2 * s) < s
    return jnp.where(lo, pltpu.roll(z, nt - s, ax), pltpu.roll(z, s, ax))


def _stage_weights(cf_ref, ell: int, scf_ref=None):
    """Stage ``ell``'s (w_self, w_partner) rows, each (1, nt) f32, from a
    lane-major coefficient slab; ``scf_ref`` ((L, 1) per-stage scales)
    dequantizes an int8 slab here, in VMEM."""
    w_self = cf_ref[2 * ell: 2 * ell + 1, :].astype(_F32)
    w_part = cf_ref[2 * ell + 1: 2 * ell + 2, :].astype(_F32)
    if scf_ref is not None:
        w_self = w_self * scf_ref[ell, 0]
        w_part = w_part * scf_ref[ell, 0]
    return w_self, w_part


def _apply_stages_fwd(z, cf_ref, strides, collect: bool = False,
                      scf_ref=None):
    """Run all stages on a resident f32 tile; optionally collect inputs.
    ``cf_ref`` is a (2L, nt) lane-major slab (``lane_major``)."""
    zs = []
    for ell, s in enumerate(strides):
        if collect:
            zs.append(z)
        w_self, w_part = _stage_weights(cf_ref, ell, scf_ref)
        z = w_self * z + w_part * _partner(z, s)
    return (z, zs) if collect else z


def _kernel(*refs,
            strides: Tuple[int, ...],
            has_din: bool, has_dout: bool, has_bias: bool,
            in_width: Optional[int], has_base: bool = False,
            quant_in: bool = False, quant_out: bool = False,
            quant_cf: bool = False):
    """Kernel body: x_ref (bb, nt), cf_ref (L, nt//2, 4), o_ref (bb, nt).

    Optional refs (in order, present when the matching flag is set):
    ``quant_in`` inserts an sx_ref ((1, 1) per-block scale) after x_ref —
    x is int8, dequantized to f32 on load in VMEM; ``quant_cf`` inserts an
    scf_ref ((L, 1) per-stage scales) after cf_ref — the coefficient slab
    is int8, dequantized per stage in VMEM; din_ref / dout_ref / bias_ref,
    each (1, nt).  ``quant_out`` adds a second output sy_ref ((1, 1)): the
    epilogue computes the block's absmax/127 scale, stores it, and stores
    the int8 requantized block to o_ref — HBM sees no f32 activation
    bytes on a fully quantized run.  All compute is f32 in VMEM
    regardless of the I/O dtype.  ``in_width`` (rectangular first run)
    zero-fills the lanes past the true input width before anything else
    touches them; a narrow OUTPUT needs no in-kernel handling — the
    partial edge tile is masked by the out-of-bounds store.  With
    ``has_base`` the first ref is the scalar-prefetch ``(1,)`` base
    feature tile (sharded windowed read) and the mask compares against
    the GLOBAL column index.
    """
    refs = list(refs)
    base = refs.pop(0)[0] if has_base else 0
    x_ref = refs.pop(0)
    sx_ref = refs.pop(0) if quant_in else None
    cf_ref = refs.pop(0)
    scf_ref = refs.pop(0) if quant_cf else None
    din_ref = refs.pop(0) if has_din else None
    dout_ref = refs.pop(0) if has_dout else None
    bias_ref = refs.pop(0) if has_bias else None
    if quant_out:
        o_ref, sy_ref = refs
    else:
        (o_ref,) = refs

    z = x_ref[...].astype(_F32)
    if quant_in:
        z = z * sx_ref[0, 0]                    # dequantize-on-load (VMEM)
    if in_width is not None:
        z = _mask_cols(z, base + pl.program_id(1), in_width)
    if has_din:
        z = z * din_ref[...].astype(_F32)       # (1, nt) broadcast over rows
    z = _apply_stages_fwd(z, cf_ref, strides, scf_ref=scf_ref)
    if has_dout:
        z = z * dout_ref[...].astype(_F32)
    if has_bias:
        z = z + bias_ref[...].astype(_F32)
    if quant_out:
        # requantize-on-store: per-block absmax scale, int8 payload.  The
        # scale convention matches kernels/quant.py (always positive).
        sy = jnp.max(jnp.abs(z)) / 127.0 + 1e-12
        sy_ref[...] = sy.reshape(1, 1)
        o_ref[...] = jnp.clip(jnp.round(z / sy), -127, 127).astype(jnp.int8)
    else:
        o_ref[...] = z.astype(o_ref.dtype)


def vmem_bytes(block_rows: int, n_tile: int, n_stages: int,
               dtype_bytes: int = 4) -> int:
    """Estimated VMEM working set of the BACKWARD kernel — the binding one,
    since forward and backward share ``block_rows``: the in-VMEM remat
    keeps all L+1 stage-input tiles PLUS the delta tile resident in f32
    until the reverse walk consumes them, on top of the x/gy/gx I/O tiles
    and two coefficient slabs (coeffs in, gcf out).  The forward needs
    strictly less (2 activation copies).  Diag/bias slabs are O(n_tile),
    negligible.

    The model keys on ONE run's (n_tile, n_stages): ops.py budgets each run
    of a plan against its own tile width and stage count (not a uniform
    n-wide worst case — see ``ops.pick_block_rows_for_plan``).  Rectangular
    boundary runs change nothing here: a masked-fill input tile occupies
    the full (block_rows, n_tile) buffer in VMEM even when the HBM operand
    is narrower."""
    act = (n_stages + 2) * block_rows * n_tile * 4   # zs (L+1) + delta, f32
    io = 3 * block_rows * n_tile * dtype_bytes       # x, gy, gx tiles
    cf = 2 * n_stages * (n_tile // 2) * 4 * 4        # coeffs + gcf
    return act + io + cf


def overlap_vmem_bytes(block_rows: int, n_tile: int, n_stages: int,
                       dtype_bytes: int = 4) -> int:
    """VMEM working set of the overlap (RDMA) kernels — the binding one is
    again the backward: the ``vmem_bytes`` stage-remat working set PLUS the
    per-block send/recv communication buffers.  The backward exchanges a
    ``(2, block_rows, n_tile)`` package per row block — the (delta, z_out)
    pair — double-buffered on BOTH ends (2 slots x send + recv), i.e.

        comm = 2 slots * 2 tensors * 2 ends * block_rows * n_tile * io_bytes

    in the activation I/O dtype (blocks travel the wire as sent), plus ONE
    extra I/O tile: the overlap backward streams x through two BlockSpec
    windows (the send-side remat reads block i while the walk-side remat
    reads block i-1), one more activation window than the three
    ``vmem_bytes`` models.  The forward ships only z_out (half the
    package) and needs strictly less; budgeting the backward keeps
    ``block_rows`` shared, exactly as ``vmem_bytes`` does for the
    non-overlap pair."""
    comm = 8 * block_rows * n_tile * dtype_bytes
    x_walk = block_rows * n_tile * dtype_bytes   # second x window (bwd)
    return vmem_bytes(block_rows, n_tile, n_stages, dtype_bytes) \
        + comm + x_walk


def block_vmem_bytes(block_rows: int, n_tile: int, n_stages: int,
                     dtype_bytes: int = 4) -> int:
    """VMEM working set of the residual-BLOCK kernels (norm prologue ->
    stack 1 -> activation -> stack 2 -> residual store) — the binding one
    is again the backward, which remats the whole chain in VMEM:
    ``vmem_bytes`` with ``n_stages = L1 + L2`` covers the two stacks'
    stage-input tiles, and on top of that the block keeps THREE more f32
    activation tiles live across the chain — the normalized x-hat tile
    (the norm backward re-reads it after both stage walks), and the
    mid-boundary pre-activation u / post-activation h pair (u feeds the
    activation derivative, h feeds the second stack's d_in grad) — plus
    the (block_rows, 1) row statistics.  Per-linear budgeting
    (``ops.pick_block_rows_for_plan`` without ``block_bufs``) misses
    these and would overcommit VMEM by ~3 tiles."""
    extra = 3 * block_rows * n_tile * 4 + block_rows * 4
    return vmem_bytes(block_rows, n_tile, n_stages, dtype_bytes) + extra


def pick_block_rows(n_tile: int, n_stages: int, dtype_bytes: int = 4,
                    budget: int = 12 * 2**20, *,
                    overlap: bool = False, block: bool = False) -> int:
    """Largest power-of-two row-block (>=8) within the VMEM budget;
    ``overlap`` budgets against ``overlap_vmem_bytes`` (the RDMA kernels'
    send/recv double buffers ride the same VMEM), ``block`` against
    ``block_vmem_bytes`` (the residual-block kernels' norm/activation/
    residual live buffers)."""
    if block:
        cost = block_vmem_bytes
    else:
        cost = overlap_vmem_bytes if overlap else vmem_bytes
    bb = 8
    while bb < 1024 and cost(bb * 2, n_tile, n_stages,
                             dtype_bytes) <= budget:
        bb *= 2
    return bb


def pick_max_tile(n: int, n_stages: int, dtype_bytes: int = 4,
                  budget: int = 12 * 2**20) -> int:
    """Feature-tile cap for tiny-row (decode) calls: the widest
    power-of-two multiple of the default 2048 cap whose backward working
    set still fits the VMEM budget at the MINIMUM row block (8).

    Decode ticks call the operator with rows = active batch slots — a
    single row block.  The default ``ops.MAX_TILE`` cap is sized for
    training row counts, where many row blocks stream through VMEM
    concurrently with wide tiles; with one 8-row block resident the same
    budget affords much wider tiles, so a schedule that plans to several
    runs at 2048 (several HBM round-trips per token) re-plans to fewer,
    wider runs — often one."""
    cap = 2048
    while cap < n and vmem_bytes(8, cap * 2, n_stages,
                                 dtype_bytes) <= budget:
        cap *= 2
    return cap


def _last_block(width: int, n_tile: int) -> int:
    """Index of the last feature block of a ``width``-wide operand."""
    return -(-width // n_tile) - 1


def _vec_spec(n_tile: int) -> pl.BlockSpec:
    """(1, n_tile) slab of an (1, n) vector, indexed by the feature tile."""
    return pl.BlockSpec((1, n_tile), lambda i, j: (0, j))


def _lift_spec(spec: pl.BlockSpec) -> pl.BlockSpec:
    """Adapt a plain BlockSpec to a scalar-prefetch grid: index maps gain
    a trailing scalar ref, which non-windowed operands ignore.  Works for
    either grid-axis order (it just drops the last argument)."""
    return pl.BlockSpec(spec.block_shape,
                        lambda *a, f=spec.index_map: f(*a[:-1]))


@functools.partial(jax.jit, static_argnames=("strides", "block_rows",
                                             "n_tile", "in_width",
                                             "out_width", "quant_out",
                                             "interpret"))
def spm_stack_kernel_call(x: jax.Array, coeffs: jax.Array,
                          d_in: Optional[jax.Array] = None,
                          d_out: Optional[jax.Array] = None,
                          bias: Optional[jax.Array] = None,
                          col_base: Optional[jax.Array] = None,
                          x_scale: Optional[jax.Array] = None,
                          coeff_scale: Optional[jax.Array] = None, *,
                          strides: Tuple[int, ...],
                          block_rows: int,
                          n_tile: int,
                          in_width: Optional[int] = None,
                          out_width: Optional[int] = None,
                          quant_out: bool = False,
                          interpret: bool = False):
    """pallas_call wrapper.  x: (B, in_width or n); coeffs: (L, n//2, 4);
    optional d_in/d_out/bias: (n,) — folded into the kernel (applied before
    the first / after the last stage, in VMEM).  ``in_width`` /
    ``out_width`` make the boundary runs rectangular-native: the input is
    zero-filled to n in VMEM (iota mask) and only the first ``out_width``
    output columns are computed (grid shrinks to ceil(out_width / n_tile)
    tiles — tile-local pairing makes the rest dead) and stored (masked
    partial edge tile).  Returns (B, out_width or n).

    Quantized I/O (kernels/quant.py conventions):

    * ``x_scale`` — x is int8 with per-(row-block, feature-tile) scales
      ``(B // block_rows, ceil(in_width / n_tile))``; each block is
      dequantized to f32 on load, in VMEM.
    * ``quant_out=True`` — the epilogue requantizes the finished block and
      returns ``(y int8, y_scale f32)`` with ``y_scale`` shaped
      ``(B // block_rows, grid feature tiles)``; chained runs feed it
      straight back as the next run's ``x_scale`` (tiles must match).
    * ``coeff_scale`` — coeffs is int8 with per-stage ``(L, 1)`` scales,
      dequantized one stage at a time in VMEM.

    ``col_base`` (sharded windowed read — requires ``in_width``, excludes
    ``out_width`` and quantized ACTIVATIONS; quantized coeffs are fine):
    a TRACED (1,) int32 base feature tile.  x is the feature-COMPLETE
    (B, in_width) operand shared by all shards; the x index map offsets
    its feature block by the base (scalar prefetch) so this shard
    reads/zero-fills exactly its n-wide window of the global operator,
    and the output is the full (B, n) shard-local slab.

    Requires: B % block_rows == 0, n % n_tile == 0, and every stride s
    satisfies n_tile % (2*s) == 0 (pairs tile-local).  ops.py guarantees
    these by padding/splitting; this function is the raw kernel entry.
    """
    B = x.shape[0]
    L, n = coeffs.shape[0], 2 * coeffs.shape[1]
    assert x.shape[-1] == (in_width if in_width is not None else n)
    assert B % block_rows == 0 and n % n_tile == 0
    for s in strides:
        assert n_tile % (2 * s) == 0, (s, n_tile)
    quant_in = x_scale is not None
    assert quant_in == (x.dtype == jnp.int8)
    has_base = col_base is not None
    assert not has_base or (in_width is not None and out_width is None)
    assert not has_base or (not quant_in and not quant_out)
    out_w = out_width if out_width is not None else n
    grid = (B // block_rows, n // n_tile if has_base
            else -(-out_w // n_tile))

    # Lanes of feature tile j are the slab columns [j * n_tile,
    # (j+1) * n_tile): each tile covers whole pair groups for every fused
    # stride.  x blocks wholly past a narrow input clamp onto its last
    # block (the in-VMEM mask zero-fills them), so no DMA leaves the array.
    x_last = _last_block(x.shape[-1], n_tile)
    x_spec = pl.BlockSpec((block_rows, n_tile),
                          lambda i, j: (i, jnp.minimum(j, x_last)))
    cf_spec = pl.BlockSpec((2 * L, n_tile), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_rows, n_tile), lambda i, j: (i, j))
    sc_spec = pl.BlockSpec((1, 1), lambda i, j: (i, j))
    scf_spec = pl.BlockSpec((L, 1), lambda i, j: (0, 0))

    operands = [x]
    in_specs = [x_spec]
    if quant_in:
        operands.append(x_scale.astype(_F32))
        in_specs.append(sc_spec)
    operands.append(lane_major(coeffs, strides))
    in_specs.append(cf_spec)
    if coeff_scale is not None:
        operands.append(coeff_scale.astype(_F32).reshape(L, 1))
        in_specs.append(scf_spec)
    for vec in (d_in, d_out, bias):
        if vec is not None:
            operands.append(vec.reshape(1, n))
            in_specs.append(_vec_spec(n_tile))

    out_specs = o_spec
    out_shape = jax.ShapeDtypeStruct(
        (B, out_w), jnp.int8 if quant_out else x.dtype)
    if quant_out:
        out_specs = [o_spec, sc_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B // block_rows, grid[1]),
                                          jnp.float32)]

    kernel = functools.partial(_kernel, strides=strides,
                               has_din=d_in is not None,
                               has_dout=d_out is not None,
                               has_bias=bias is not None,
                               in_width=in_width, has_base=has_base,
                               quant_in=quant_in, quant_out=quant_out,
                               quant_cf=coeff_scale is not None)
    if has_base:
        # Scalar prefetch: every index map gains a trailing base ref; only
        # the x map consumes it (blocks past the operand edge clamp; the
        # in-VMEM mask against the global column zero-fills them).
        in_specs = [_lift_spec(s) for s in in_specs]
        in_specs[0] = pl.BlockSpec(
            x_spec.block_shape,
            lambda i, j, b: (i, jnp.minimum(b[0] + j, x_last)))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=in_specs, out_specs=_lift_spec(o_spec)),
            out_shape=jax.ShapeDtypeStruct((B, n), x.dtype),
            interpret=interpret,
        )(col_base.astype(jnp.int32), *operands)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# fused backward kernel
# ---------------------------------------------------------------------------
#
# Training is 2/3 backward; without a fused backward the forward fusion win
# is capped at 1.5x end-to-end.  The backward kernel recomputes the stage
# inputs IN VMEM from the x tile (no HBM traffic for intermediates — the
# Pallas analogue of remat), then walks the stages in reverse applying the
# paper's closed forms: delta <- B_l^T delta (eqs. 12-13) and the rank-1 pair
# accumulations for (a, b, c, d) grads (eq. 14).  The folded diag/bias grads
# ride the same pass: g_bias/g_dout fall out of gy (and the recomputed z_L)
# before the stage walk, g_din out of delta_0 after it.  All parameter-
# gradient partials are accumulated across batch tiles in their output
# blocks; the grid is therefore (feature, batch) with batch as the MINOR
# axis, so for a fixed feature tile every batch step maps to the SAME
# output block on consecutive grid iterations — the documented Pallas
# reduction pattern (the block stays resident in VMEM between consecutive
# revisits; accumulating across a non-minor axis would read back a flushed
# buffer on real TPU): init at batch step 0, accumulate after.

def _stage_walk_bwd(zs, delta, cf_ref, strides: Tuple[int, ...],
                    scf_ref=None):
    """Reverse walk over one run's stages from the collected stage-input
    tiles ``zs``: the eq. 14 pair grads (reduced over the batch-tile rows)
    and delta <- B^T delta (eqs. 12-13), in the lane-major form

        g_self    = sum_rows delta * z
        g_partner = sum_rows delta * z[partner]
        delta    <- w_self * delta + (w_partner * delta)[partner]

    Returns ``(delta_0, rows)`` with ``rows`` the 2L (1, nt) grad rows in
    slab order (``_acc_rows`` stores them) — shared by the plain, block
    and overlap backward kernels.  ``scf_ref`` dequantizes an int8
    coefficient slab in VMEM (the grads stay f32 in DEQUANTIZED units —
    the grads of the values the forward actually used)."""
    rows = [None] * (2 * len(strides))
    for ell in range(len(strides) - 1, -1, -1):
        s = strides[ell]
        w_self, w_part = _stage_weights(cf_ref, ell, scf_ref)
        z = zs[ell]
        rows[2 * ell] = jnp.sum(delta * z, axis=0, keepdims=True)
        rows[2 * ell + 1] = jnp.sum(delta * _partner(z, s), axis=0,
                                    keepdims=True)
        delta = w_self * delta + _partner(w_part * delta, s)
    return delta, rows


def _acc_rows(ref, rows, first):
    """Accumulate (1, nt) grad rows into a lane-major slab output across
    consecutive revisits of its block: zeroed on the ``first`` visit."""
    @pl.when(first)
    def _init():
        ref[...] = jnp.zeros(ref.shape, ref.dtype)

    for r, row in enumerate(rows):
        ref[r: r + 1, :] += row


def _bwd_kernel(*refs,
                strides: Tuple[int, ...],
                has_din: bool, has_dout: bool, has_bias: bool,
                in_width: Optional[int], out_width: Optional[int],
                has_base: bool = False, n_zero_init: int = 0,
                quant_in: bool = False, quant_cf: bool = False):
    refs = list(refs)
    base = refs.pop(0)[0] if has_base else 0
    x_ref = refs.pop(0)
    sx_ref = refs.pop(0) if quant_in else None
    cf_ref = refs.pop(0)
    scf_ref = refs.pop(0) if quant_cf else None
    gy_ref = refs.pop(0)
    din_ref = refs.pop(0) if has_din else None
    dout_ref = refs.pop(0) if has_dout else None
    if n_zero_init:
        del refs[:n_zero_init]       # aliased zero-init operands, unread
    gx_ref = refs.pop(0)
    gcf_ref = refs.pop(0)
    gdin_ref = refs.pop(0) if has_din else None
    gdout_ref = refs.pop(0) if has_dout else None
    gbias_ref = refs.pop(0) if has_bias else None

    bb, nt = x_ref.shape
    L = len(strides)
    # feature tile: major grid axis.  ``base`` shifts it to the GLOBAL
    # feature tile in the sharded windowed mode (0 otherwise), so the
    # in_width/out_width masks below always compare global columns.
    j = base + pl.program_id(0)

    # recompute stage inputs in VMEM (forward remat), incl. the d_in fold.
    # Rectangular first run: lanes past in_width are zero-filled exactly as
    # the forward saw them, so the remat AND every grad that multiplies by
    # x (g_din, the eq. 14 coefficient grads) see zeros on padded lanes.
    # A quantized saved-x (int8 + per-block scale) dequantizes on load, so
    # the remat replays EXACTLY the activations the quantized forward
    # produced — the backward is the true gradient of the quantized net.
    x_raw = x_ref[...].astype(_F32)
    if quant_in:
        x_raw = x_raw * sx_ref[0, 0]
    if in_width is not None:
        x_raw = _mask_cols(x_raw, j, in_width)
    z0 = x_raw * din_ref[...].astype(_F32) if has_din else x_raw
    z_last, zs = _apply_stages_fwd(z0, cf_ref, strides, collect=True,
                                   scf_ref=scf_ref)

    # Rectangular last run: the sliced-away output columns carry no
    # cotangent, so masking gy to out_width zeroes their contribution to
    # g_bias / g_dout and to the stage walk below.
    gy = gy_ref[...].astype(_F32)
    if out_width is not None:
        gy = _mask_cols(gy, j, out_width)
    i = pl.program_id(1)  # batch step: minor grid axis (see note above)

    def _acc(ref, tile):
        @pl.when(i == 0)
        def _init():
            ref[...] = tile

        @pl.when(i > 0)
        def _add():
            ref[...] += tile

    if has_bias:
        _acc(gbias_ref, jnp.sum(gy, axis=0).reshape(1, nt))
    if has_dout:
        _acc(gdout_ref, jnp.sum(gy * z_last, axis=0).reshape(1, nt))
        delta = gy * dout_ref[...].astype(_F32)
    else:
        delta = gy

    delta, g_rows = _stage_walk_bwd(zs, delta, cf_ref, strides,
                                    scf_ref=scf_ref)

    if has_din:
        _acc(gdin_ref, jnp.sum(delta * x_raw, axis=0).reshape(1, nt))
        delta = delta * din_ref[...].astype(_F32)
    gx_ref[...] = delta.astype(gx_ref.dtype)
    _acc_rows(gcf_ref, g_rows, i == 0)                 # (2L, nt)


@functools.partial(jax.jit, static_argnames=("strides", "block_rows",
                                             "n_tile", "has_bias",
                                             "in_width", "out_width",
                                             "dead_from", "interpret"))
def spm_stack_bwd_kernel_call(x: jax.Array, coeffs: jax.Array,
                              gy: jax.Array,
                              d_in: Optional[jax.Array] = None,
                              d_out: Optional[jax.Array] = None,
                              col_base: Optional[jax.Array] = None,
                              x_scale: Optional[jax.Array] = None,
                              coeff_scale: Optional[jax.Array] = None, *,
                              strides: Tuple[int, ...],
                              block_rows: int,
                              n_tile: int,
                              has_bias: bool = False,
                              in_width: Optional[int] = None,
                              out_width: Optional[int] = None,
                              dead_from: Optional[int] = None,
                              interpret: bool = False):
    """Fused backward for (optionally) the full operator.

    Always returns ``(g_x (B, in_width or n), g_coeffs (L, n//2, 4) f32)``
    followed by ``g_din (n,)`` if ``d_in`` was given, ``g_dout (n,)`` if
    ``d_out`` was given, and ``g_bias (n,)`` if ``has_bias`` (the bias value
    itself is not needed for its grad).  All parameter grads are f32.

    Quantized operands (kernels/quant.py conventions): ``x_scale`` marks a
    saved-x that is int8 with per-(row-block, feature-tile) scales —
    dequantized on load, so the in-VMEM remat replays exactly the
    activations the quantized forward produced (g_x then comes back in
    the GY dtype, never int8 — cotangents are not quantized).
    ``coeff_scale`` marks an int8 coefficient table with per-stage
    ``(L, 1)`` scales dequantized in VMEM; the f32 gcf output is the grad
    of the DEQUANTIZED values, bitwise what a pre-dequantized f32 table
    would produce.

    Rectangular boundaries: ``x`` is (B, in_width) and ``gy`` is
    (B, out_width) when set; both are masked to exact zeros past their
    width in VMEM, so padded lanes contribute exact zeros to the
    coefficient, diag, and bias grads.

    Dead-tile skip: a feature tile whose columns all sit at or past
    ``out_width`` carries an all-zero masked gy, and tile-local pairing
    makes EVERY grad it produces an exact zero — the grid visits only
    ``ceil(out_width / n_tile)`` feature tiles, and the skipped tiles'
    parameter-grad / g_x blocks are zero-initialized by aliasing pre-zeroed
    operands onto the outputs (``input_output_aliases``: an unvisited
    block keeps its input value).  ``dead_from`` declares the same
    all-zero-cotangent property for an interior run of a multi-run plan
    (its gy is the downstream run's g_x, exactly zero from the first
    column that run skipped) without implying a narrow gy operand.

    ``g_x`` comes back (B, in_width) only when ceil(in_width / n_tile)
    covers at least the visited tiles; when ``in_width`` leaves whole
    VISITED feature tiles past the array edge it comes back widened to the
    visited width and the CALLER slices — a fully out-of-bounds output
    block is not masked but CLAMPED onto the last valid block (both
    interpret mode and Mosaic clamp block indices), which would corrupt
    valid g_x columns.

    ``col_base`` (sharded windowed mode — see the forward kernel): a
    TRACED (1,) int32 base feature tile.  ``in_width``/``out_width``
    become GLOBAL widths; the matching operand (x / gy) is the
    feature-complete global array read through an offset index map, masks
    compare global columns, g_x is the full (B, n) shard-local slab, and
    the grid keeps every local tile (it must be SPMD-uniform across
    shards, so the skip is single-device only).
    """
    B = x.shape[0]
    L, n = coeffs.shape[0], 2 * coeffs.shape[1]
    has_base = col_base is not None
    assert not (has_base and dead_from is not None)
    quant_in = x_scale is not None
    assert quant_in == (x.dtype == jnp.int8)
    assert not (has_base and quant_in)
    x_windowed = has_base and in_width is not None
    gy_windowed = has_base and out_width is not None
    in_w = in_width if in_width is not None else n
    assert x.shape[-1] == in_w
    assert gy.shape[-1] == (out_width if out_width is not None else n)
    assert B % block_rows == 0 and n % n_tile == 0
    n_tiles = n // n_tile

    # Visited feature tiles: every tile from the first all-dead column on
    # is skipped (single-device only: the sharded grid is SPMD-uniform).
    live = n
    if out_width is not None:
        live = min(live, out_width)
    if dead_from is not None:
        live = min(live, dead_from)
    vis = n_tiles if has_base else min(n_tiles, -(-live // n_tile))

    gx_w = n if x_windowed else in_w
    if not x_windowed and -(-gx_w // n_tile) < vis:
        gx_w = vis * n_tile  # see docstring: narrow g_x would alias
        #                      clamped stores; the caller slices
    # batch is the MINOR grid axis: parameter-grad blocks (indexed by the
    # feature tile only) are revisited on consecutive iterations, which is
    # required for the in-block accumulation to be valid on real TPU.
    grid = (vis, B // block_rows)

    def read_spec(width, windowed=False):
        # input blocks wholly past a narrow operand clamp onto its last
        # block (masked to zeros in VMEM); outputs never clamp
        last = _last_block(width, n_tile)
        if windowed:
            return pl.BlockSpec((block_rows, n_tile), lambda j, i, b: (
                i, jnp.minimum(b[0] + j, last)))
        return pl.BlockSpec((block_rows, n_tile),
                            lambda j, i: (i, jnp.minimum(j, last)))

    act_spec = pl.BlockSpec((block_rows, n_tile), lambda j, i: (i, j))
    cf_spec = pl.BlockSpec((2 * L, n_tile), lambda j, i: (0, j))
    vec_spec = pl.BlockSpec((1, n_tile), lambda j, i: (0, j))
    sc_spec = pl.BlockSpec((1, 1), lambda j, i: (i, j))
    scf_spec = pl.BlockSpec((L, 1), lambda j, i: (0, 0))

    operands = [x]
    in_specs = [read_spec(x.shape[-1])]
    if quant_in:
        operands.append(x_scale.astype(jnp.float32))
        in_specs.append(sc_spec)
    operands.append(lane_major(coeffs, strides))
    in_specs.append(cf_spec)
    if coeff_scale is not None:
        operands.append(coeff_scale.astype(jnp.float32).reshape(L, 1))
        in_specs.append(scf_spec)
    operands.append(gy)
    in_specs.append(read_spec(gy.shape[-1]))
    for vec in (d_in, d_out):
        if vec is not None:
            operands.append(vec.reshape(1, n))
            in_specs.append(vec_spec)

    gx_dt = gy.dtype if quant_in else x.dtype
    out_specs = [act_spec, cf_spec]
    out_shape = [jax.ShapeDtypeStruct((B, gx_w), gx_dt),
                 jax.ShapeDtypeStruct((2 * L, n), jnp.float32)]
    for present in (d_in is not None, d_out is not None, has_bias):
        if present:
            out_specs.append(vec_spec)
            out_shape.append(jax.ShapeDtypeStruct((1, n), jnp.float32))

    # Zero-init every output owning blocks the shrunk grid never visits by
    # aliasing a zeros operand onto it: g_x only when it is wider than the
    # visited region, parameter grads whenever any tile is skipped.  The
    # zeros operands sit at the END of the input list (the kernel body
    # skips ``n_zero_init`` refs there).
    aliases = {}
    n_zero_init = 0
    if vis < n_tiles:
        for o, (spec, sh) in enumerate(zip(out_specs, out_shape)):
            if o == 0 and -(-gx_w // n_tile) <= vis:
                continue
            aliases[len(operands)] = o
            operands.append(jnp.zeros(sh.shape, sh.dtype))
            in_specs.append(spec)
            n_zero_init += 1

    kernel = functools.partial(_bwd_kernel, strides=strides,
                               has_din=d_in is not None,
                               has_dout=d_out is not None,
                               has_bias=has_bias,
                               in_width=in_width, out_width=out_width,
                               has_base=has_base, n_zero_init=n_zero_init,
                               quant_in=quant_in,
                               quant_cf=coeff_scale is not None)
    if has_base:
        # Scalar prefetch: every index map gains a trailing base ref; only
        # the windowed operands consume it (offset feature block).
        in_specs = [_lift_spec(s) for s in in_specs]
        gy_idx = 2 + (1 if coeff_scale is not None else 0)
        if x_windowed:
            in_specs[0] = read_spec(x.shape[-1], windowed=True)
        if gy_windowed:
            in_specs[gy_idx] = read_spec(gy.shape[-1], windowed=True)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=in_specs,
                out_specs=[_lift_spec(s) for s in out_specs]),
            out_shape=out_shape,
            interpret=interpret,
        )(col_base.astype(jnp.int32), *operands)
    else:
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
        )(*operands)
    gx, gcf = out[0], pair_table(out[1], strides)
    vec_grads = tuple(v.reshape(n) for v in out[2:])
    return (gx, gcf) + vec_grads


# ---------------------------------------------------------------------------
# residual-block (megakernel) pair: norm -> SPM -> act -> SPM -> residual
# ---------------------------------------------------------------------------
#
# The per-linear fused operator still pays an HBM round-trip at every
# block boundary: norm reads+writes the activation before the up
# projection, the activation reads+writes between the two linears, and
# the residual add reads+writes after the down projection — >=2 extra
# full-activation round-trips per transformer block that the O(nL)
# operator itself no longer needs.  These kernels lower the WHOLE
# residual block as one fused region:
#
#   prologue   RMS row statistics + gamma scale, in VMEM
#   stack 1    d_in -> stages -> d_out (+bias): the up projection
#   epilogue   activation (relu / silu / gelu, closed form both ways)
#   stack 2    the down projection, fed without leaving VMEM
#   store      + residual, masked to out_width
#
# Eligibility (core/eligibility.block_fusion_eligible) guarantees both
# stacks plan to a SINGLE full-width run (every stride s of either stack
# has n % (2s) == 0 and n <= BLOCK_MAX_TILE), so the grid is row blocks
# only — the feature axis never re-tiles between the stacks and the mid
# activation never touches HBM.
#
# Backward remats from row statistics: the forward saves ONLY the raw x
# and the (rows, 1) rstd — the normalized input, both stacks' stage
# inputs, and the mid activation are all recomputed in VMEM (the Pallas
# remat idiom of the per-linear backward, extended over the whole
# chain), then one reverse walk produces every grad closed-form:
# bias2/dout2 from gy, the eq. 12-14 walk of stack 2, the activation
# derivative at the rematted u, bias1/dout1/stack 1, gamma from the
# rematted x-hat, and the RMS-norm input grad
#
#   g_x = rstd * (g_xhat - xhat * mean(g_xhat * xhat))  (+ gy residual)
#
# Dead-lane discipline: x is masked to in_width before the row
# statistics (the mean divides by in_width, not n), the mid boundary is
# masked to mid_width before the activation (act(0) = 0 for every
# BLOCK_ACTIVATIONS member, so dead lanes enter stack 2 as exact zeros —
# bitwise what the unfused rectangular composition feeds it), and gy is
# masked to out_width; every parameter grad is therefore exactly zero on
# padded lanes.  The grid is 1-D over row blocks, so the parameter-grad
# outputs (indexed to block 0) are revisited on consecutive iterations —
# the same documented TPU reduction pattern as the per-linear backward,
# with no zero-init aliasing needed (block 0 is always visited at i=0).

def _act_fwd(u, activation: Optional[str]):
    """Closed-form block-epilogue activation on a resident f32 tile.
    ``None`` is the identity (norm-prologue-only entries, e.g. fused
    qkv).  Every member maps 0 -> 0, which the dead-lane masking relies
    on."""
    if activation == "relu":
        return jnp.maximum(u, 0.0)
    if activation == "silu":
        return u * jax.nn.sigmoid(u)
    if activation == "gelu":
        return jax.nn.gelu(u)       # tanh approximation (jax default)
    return u


def _act_grad(u, activation: Optional[str]):
    """Closed-form derivative of ``_act_fwd`` at the rematted
    pre-activation ``u`` — the backward never stores the activation."""
    if activation == "relu":
        return jnp.where(u > 0, 1.0, 0.0)
    if activation == "silu":
        sg = jax.nn.sigmoid(u)
        return sg * (1.0 + u * (1.0 - sg))
    if activation == "gelu":
        # d/du of the tanh-approx gelu 0.5*u*(1 + tanh(k*(u + 0.044715 u^3)))
        k = 0.7978845608028654      # sqrt(2/pi)
        t = jnp.tanh(k * (u + 0.044715 * u * u * u))
        return (0.5 * (1.0 + t)
                + 0.5 * u * (1.0 - t * t) * k
                * (1.0 + 3 * 0.044715 * u * u))
    return jnp.ones_like(u)


def _block_kernel(*refs,
                  strides1: Tuple[int, ...],
                  strides2: Optional[Tuple[int, ...]],
                  activation: Optional[str],
                  has_norm: bool, has_bias1: bool, has_bias2: bool,
                  residual: bool, in_width: int, mid_width: int,
                  out_width: int, eps: float):
    refs = list(refs)
    x_ref = refs.pop(0)
    g_ref = refs.pop(0) if has_norm else None
    cf1_ref = refs.pop(0)
    din1_ref, dout1_ref = refs.pop(0), refs.pop(0)
    bias1_ref = refs.pop(0) if has_bias1 else None
    if strides2 is not None:
        cf2_ref = refs.pop(0)
        din2_ref, dout2_ref = refs.pop(0), refs.pop(0)
        bias2_ref = refs.pop(0) if has_bias2 else None
    if has_norm:
        o_ref, rstd_ref = refs
    else:
        (o_ref,) = refs

    x_raw = _mask_cols(x_ref[...].astype(_F32), 0, in_width)
    if has_norm:
        # row statistics over the TRUE input width (padded lanes are 0)
        var = jnp.sum(x_raw * x_raw, axis=1, keepdims=True) / in_width
        rstd = jax.lax.rsqrt(var + eps)
        rstd_ref[...] = rstd
        z = x_raw * rstd * g_ref[...].astype(_F32)
    else:
        z = x_raw
    z = z * din1_ref[...].astype(_F32)
    z = _apply_stages_fwd(z, cf1_ref, strides1)
    z = z * dout1_ref[...].astype(_F32)
    if has_bias1:
        z = z + bias1_ref[...].astype(_F32)
    if strides2 is not None:
        # mask BEFORE the activation: bias1 contaminates lanes past
        # mid_width, and act(0) = 0 keeps them exact zeros into stack 2
        z = _act_fwd(_mask_cols(z, 0, mid_width), activation)
        z = z * din2_ref[...].astype(_F32)
        z = _apply_stages_fwd(z, cf2_ref, strides2)
        z = z * dout2_ref[...].astype(_F32)
        if has_bias2:
            z = z + bias2_ref[...].astype(_F32)
    elif activation is not None:
        z = _act_fwd(_mask_cols(z, 0, mid_width), activation)
    if residual:
        z = z + x_raw
    o_ref[...] = z.astype(o_ref.dtype)


def _block_bwd_kernel(*refs,
                      strides1: Tuple[int, ...],
                      strides2: Optional[Tuple[int, ...]],
                      activation: Optional[str],
                      has_norm: bool, has_bias1: bool, has_bias2: bool,
                      residual: bool, in_width: int, mid_width: int,
                      out_width: int):
    refs = list(refs)
    x_ref = refs.pop(0)
    g_ref = refs.pop(0) if has_norm else None
    rstd_ref = refs.pop(0) if has_norm else None
    cf1_ref = refs.pop(0)
    din1_ref, dout1_ref = refs.pop(0), refs.pop(0)
    bias1_ref = refs.pop(0) if has_bias1 else None
    if strides2 is not None:
        cf2_ref = refs.pop(0)
        din2_ref, dout2_ref = refs.pop(0), refs.pop(0)
        bias2_ref = refs.pop(0) if has_bias2 else None
    gy_ref = refs.pop(0)
    gx_ref = refs.pop(0)
    ggam_ref = refs.pop(0) if has_norm else None
    gcf1_ref, gdin1_ref, gdout1_ref = (refs.pop(0), refs.pop(0),
                                       refs.pop(0))
    gbias1_ref = refs.pop(0) if has_bias1 else None
    if strides2 is not None:
        gcf2_ref, gdin2_ref, gdout2_ref = (refs.pop(0), refs.pop(0),
                                           refs.pop(0))
        gbias2_ref = refs.pop(0) if has_bias2 else None

    i = pl.program_id(0)
    bb, nt = x_ref.shape

    def _acc(ref, tile):
        @pl.when(i == 0)
        def _init():
            ref[...] = tile

        @pl.when(i > 0)
        def _add():
            ref[...] += tile

    # ---- remat the whole block forward in VMEM (norm from saved rstd) ----
    x_raw = _mask_cols(x_ref[...].astype(_F32), 0, in_width)
    if has_norm:
        rstd = rstd_ref[...]                       # (bb, 1) f32, saved
        xh = x_raw * rstd
        z0 = xh * g_ref[...].astype(_F32)
    else:
        z0 = x_raw
    t1 = z0 * din1_ref[...].astype(_F32)
    z1_last, zs1 = _apply_stages_fwd(t1, cf1_ref, strides1, collect=True)
    u = z1_last * dout1_ref[...].astype(_F32)
    if has_bias1:
        u = u + bias1_ref[...].astype(_F32)
    if strides2 is not None:
        u = _mask_cols(u, 0, mid_width)
        h = _act_fwd(u, activation)
        t2 = h * din2_ref[...].astype(_F32)
        z2_last, zs2 = _apply_stages_fwd(t2, cf2_ref, strides2,
                                         collect=True)
    elif activation is not None:
        u = _mask_cols(u, 0, mid_width)

    gy = _mask_cols(gy_ref[...].astype(_F32), 0, out_width)

    # ---- reverse walk ----
    if strides2 is not None:
        if has_bias2:
            _acc(gbias2_ref, jnp.sum(gy, axis=0).reshape(1, nt))
        _acc(gdout2_ref, jnp.sum(gy * z2_last, axis=0).reshape(1, nt))
        delta = gy * dout2_ref[...].astype(_F32)
        delta, g_rows2 = _stage_walk_bwd(zs2, delta, cf2_ref, strides2)
        _acc_rows(gcf2_ref, g_rows2, i == 0)
        _acc(gdin2_ref, jnp.sum(delta * h, axis=0).reshape(1, nt))
        dh = _mask_cols(delta * din2_ref[...].astype(_F32), 0, mid_width)
        du = dh * _act_grad(u, activation)
    elif activation is not None:
        du = gy * _act_grad(u, activation)
    else:
        du = gy
    if has_bias1:
        _acc(gbias1_ref, jnp.sum(du, axis=0).reshape(1, nt))
    _acc(gdout1_ref, jnp.sum(du * z1_last, axis=0).reshape(1, nt))
    delta = du * dout1_ref[...].astype(_F32)
    delta, g_rows1 = _stage_walk_bwd(zs1, delta, cf1_ref, strides1)
    _acc_rows(gcf1_ref, g_rows1, i == 0)
    _acc(gdin1_ref, jnp.sum(delta * z0, axis=0).reshape(1, nt))
    dz0 = _mask_cols(delta * din1_ref[...].astype(_F32), 0, in_width)
    if has_norm:
        _acc(ggam_ref, jnp.sum(dz0 * xh, axis=0).reshape(1, nt))
        gxh = dz0 * g_ref[...].astype(_F32)
        mean = jnp.sum(gxh * xh, axis=1, keepdims=True) / in_width
        gx = rstd * (gxh - xh * mean)
    else:
        gx = dz0
    if residual:
        gx = gx + gy
    gx_ref[...] = gx.astype(gx_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "strides1", "strides2", "activation", "block_rows", "residual",
    "in_width", "mid_width", "out_width", "eps", "interpret"))
def spm_block_kernel_call(x: jax.Array, coeffs1: jax.Array,
                          d_in1: jax.Array, d_out1: jax.Array,
                          bias1: Optional[jax.Array] = None,
                          gamma: Optional[jax.Array] = None,
                          coeffs2: Optional[jax.Array] = None,
                          d_in2: Optional[jax.Array] = None,
                          d_out2: Optional[jax.Array] = None,
                          bias2: Optional[jax.Array] = None, *,
                          strides1: Tuple[int, ...],
                          strides2: Optional[Tuple[int, ...]] = None,
                          activation: Optional[str] = None,
                          block_rows: int,
                          residual: bool = False,
                          in_width: int, mid_width: int, out_width: int,
                          eps: float = 1e-6,
                          interpret: bool = False):
    """Residual-block megakernel forward: ONE pallas_call lowering
    norm -> stack 1 -> activation -> stack 2 -> (+residual) store.

    x: (B, in_width); gamma: (n,) RMS scale zero-padded past ``in_width``
    (None skips the norm prologue); coeffs1/coeffs2: (L, n//2, 4) stage
    slabs of the up / down projections, with their (n,) d_in / d_out /
    optional bias; ``strides2=None`` ends the chain after stack 1 (the
    norm-prologue-only fused-qkv entry).  Both stacks must satisfy
    ``block_fusion_eligible`` (single full-width run each) — asserted
    here.  Returns ``y (B, out_width)`` or ``(y, rstd (B, 1) f32)`` with
    the norm prologue; rstd is the ONLY extra forward residual the
    backward needs (remat-from-row-stats).
    """
    B = x.shape[0]
    L1, n = coeffs1.shape[0], 2 * coeffs1.shape[1]
    assert x.shape[-1] == in_width and B % block_rows == 0
    for s in strides1 + (strides2 or ()):
        assert n % (2 * s) == 0, (s, n)
    if strides2 is not None:
        assert 2 * coeffs2.shape[1] == n
    if residual:
        assert out_width == in_width, (out_width, in_width)
    has_norm = gamma is not None
    grid = (B // block_rows,)

    row_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))

    def _cf_spec(L):
        return pl.BlockSpec((2 * L, n), lambda i: (0, 0))

    operands, in_specs = [x], [row_spec]
    if has_norm:
        operands.append(gamma.reshape(1, n))
        in_specs.append(vec_spec)
    operands += [lane_major(coeffs1, strides1), d_in1.reshape(1, n),
                 d_out1.reshape(1, n)]
    in_specs += [_cf_spec(L1), vec_spec, vec_spec]
    if bias1 is not None:
        operands.append(bias1.reshape(1, n))
        in_specs.append(vec_spec)
    if strides2 is not None:
        operands += [lane_major(coeffs2, strides2), d_in2.reshape(1, n),
                     d_out2.reshape(1, n)]
        in_specs += [_cf_spec(coeffs2.shape[0]), vec_spec, vec_spec]
        if bias2 is not None:
            operands.append(bias2.reshape(1, n))
            in_specs.append(vec_spec)

    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((B, out_width), x.dtype)]
    if has_norm:
        out_specs.append(pl.BlockSpec((block_rows, 1), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, 1), jnp.float32))

    kernel = functools.partial(
        _block_kernel, strides1=strides1, strides2=strides2,
        activation=activation, has_norm=has_norm,
        has_bias1=bias1 is not None, has_bias2=bias2 is not None,
        residual=residual, in_width=in_width, mid_width=mid_width,
        out_width=out_width, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if has_norm else out_specs[0],
        out_shape=out_shape if has_norm else out_shape[0],
        interpret=interpret,
    )(*operands)
    return out if has_norm else (out,)


@functools.partial(jax.jit, static_argnames=(
    "strides1", "strides2", "activation", "block_rows", "residual",
    "in_width", "mid_width", "out_width", "interpret"))
def spm_block_bwd_kernel_call(x: jax.Array, gy: jax.Array,
                              coeffs1: jax.Array,
                              d_in1: jax.Array, d_out1: jax.Array,
                              bias1: Optional[jax.Array] = None,
                              gamma: Optional[jax.Array] = None,
                              rstd: Optional[jax.Array] = None,
                              coeffs2: Optional[jax.Array] = None,
                              d_in2: Optional[jax.Array] = None,
                              d_out2: Optional[jax.Array] = None,
                              bias2: Optional[jax.Array] = None, *,
                              strides1: Tuple[int, ...],
                              strides2: Optional[Tuple[int, ...]] = None,
                              activation: Optional[str] = None,
                              block_rows: int,
                              residual: bool = False,
                              in_width: int, mid_width: int,
                              out_width: int,
                              interpret: bool = False):
    """Residual-block megakernel backward: ONE pallas_call from the raw
    saved x and the (B, 1) row statistics — the normalized input, both
    stacks' stage inputs, and the mid activation are all rematted in
    VMEM (never stored by the forward), then one reverse walk emits
    every grad closed-form.  ``bias1``/``bias2`` are needed as INPUTS
    (the rematted pre-activation includes them); ``rstd`` is required
    iff ``gamma`` is given.

    Returns ``(g_x (B, in_width), [g_gamma (n,)], g_coeffs1, g_din1,
    g_dout1, [g_bias1], [g_coeffs2, g_din2, g_dout2, [g_bias2]])`` —
    bracketed entries present when the matching operand was.  All
    parameter grads are f32, exactly zero on padded lanes.
    """
    B = x.shape[0]
    L1, n = coeffs1.shape[0], 2 * coeffs1.shape[1]
    assert x.shape[-1] == in_width and gy.shape[-1] == out_width
    assert B % block_rows == 0
    has_norm = gamma is not None
    assert has_norm == (rstd is not None)
    grid = (B // block_rows,)

    row_spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    rs_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))

    def _cf_spec(L):
        return pl.BlockSpec((2 * L, n), lambda i: (0, 0))

    operands, in_specs = [x], [row_spec]
    if has_norm:
        operands += [gamma.reshape(1, n), rstd.astype(jnp.float32)]
        in_specs += [vec_spec, rs_spec]
    operands += [lane_major(coeffs1, strides1), d_in1.reshape(1, n),
                 d_out1.reshape(1, n)]
    in_specs += [_cf_spec(L1), vec_spec, vec_spec]
    if bias1 is not None:
        operands.append(bias1.reshape(1, n))
        in_specs.append(vec_spec)
    if strides2 is not None:
        operands += [lane_major(coeffs2, strides2), d_in2.reshape(1, n),
                     d_out2.reshape(1, n)]
        in_specs += [_cf_spec(coeffs2.shape[0]), vec_spec, vec_spec]
        if bias2 is not None:
            operands.append(bias2.reshape(1, n))
            in_specs.append(vec_spec)
    operands.append(gy)
    in_specs.append(row_spec)

    # g_x first, then parameter grads (all indexed to block 0 — the 1-D
    # row grid revisits them every iteration, accumulation-safe)
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((B, in_width), x.dtype)]

    def _vec_out():
        out_specs.append(vec_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, n), jnp.float32))

    cf_outs = {}                                   # output slot -> strides

    def _cf_out(L, strides):
        cf_outs[len(out_specs)] = strides
        out_specs.append(_cf_spec(L))
        out_shape.append(jax.ShapeDtypeStruct((2 * L, n), jnp.float32))

    if has_norm:
        _vec_out()                                 # g_gamma
    _cf_out(L1, strides1)
    _vec_out()                                     # g_din1
    _vec_out()                                     # g_dout1
    if bias1 is not None:
        _vec_out()
    if strides2 is not None:
        _cf_out(coeffs2.shape[0], strides2)
        _vec_out()                                 # g_din2
        _vec_out()                                 # g_dout2
        if bias2 is not None:
            _vec_out()

    kernel = functools.partial(
        _block_bwd_kernel, strides1=strides1, strides2=strides2,
        activation=activation, has_norm=has_norm,
        has_bias1=bias1 is not None, has_bias2=bias2 is not None,
        residual=residual, in_width=in_width, mid_width=mid_width,
        out_width=out_width)
    out = list(pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands))
    # flatten the (1, n) vector grads to (n,); cf grads back to pair tables
    return (out[0],) + tuple(
        pair_table(v, cf_outs[o]) if o in cf_outs else v.reshape(n)
        for o, v in enumerate(out) if o > 0)


# ---------------------------------------------------------------------------
# overlap (RDMA) kernels: fused {local run -> cross exchange -> 2x2 mix}
# ---------------------------------------------------------------------------
#
# The distributed executor's cross stages were one full-slab ppermute each:
# the whole (rows, n_local) slab had to finish its local kernel run before
# a single byte moved, so the ICI time was fully exposed.  These kernels
# restructure one {shard-local run -> cross stage} pair into a row-block
# pipeline INSIDE one pallas_call: the grid walks row blocks, block i's
# partner-half remote copy (pltpu.make_async_remote_copy over the mesh)
# starts the moment its local mix finishes, and the cross 2x2 mix is the
# receiving epilogue of iteration i+1 — so block i's exchange flies while
# block i+1 computes, double-buffered through two VMEM send/recv slots.
#
# Roles are resolved OUTSIDE the kernel: the shard body passes
# (mix_a, mix_b) with y = mix_a * z + mix_b * z_partner — (a, b) on the
# low partner, (d, c) on the high — so the kernel is role-free and the
# same program runs SPMD on every shard.  The partner's mesh coordinates
# arrive via scalar prefetch (they depend on jax.lax.axis_index, traced
# inside shard_map).
#
# Flow control (per slot s = i % 2):
#   * send side: before reusing slot s at iteration i >= 2, wait for our
#     own send from s to drain (wait_send) AND for one CREDIT — the
#     partner signals our capacity semaphore after consuming the block we
#     previously landed in ITS recv slot s, so a fast sender can never
#     overwrite an unconsumed remote buffer;
#   * recv side: iteration i consumes block i-1 (wait_recv on slot
#     (i-1) % 2), applies the mix epilogue, stores, and signals the credit.
#   * epilogue (iteration n_blocks): drain the last two sends and the two
#     unconsumed credits so every semaphore ends at zero.
#
# The BACKWARD kernel replays the same pipeline in reverse roles: the
# partner exchange is its own transpose, so each block SENDS the
# (delta, z_out) package — z_out rematerialized in VMEM from the local
# run's saved input (the forward never wrote it to HBM) — and the
# receiving iteration applies the transpose mix
# delta_mid = u * delta + v * delta_partner as its PROLOGUE, accumulates
# the role-owned cross-coefficient sums (s_own = sum delta*z_out,
# s_swp = sum delta*z_partner), then walks the local stages in reverse
# (shared _stage_walk_bwd).  The local forward runs twice per block (once
# for the send-side remat, once collecting stage inputs for the walk) —
# deliberate: the recompute is exactly the VPU work the in-flight
# exchange hides under, and it keeps the VMEM working set at one block.
#
# There is NO interpret realization of make_async_remote_copy, so these
# kernels are TPU-compile-only (core/eligibility.resolve_rdma); the
# per-block ppermute transport in parallel/spm_shard.py runs the identical
# schedule everywhere else and is what the parity tests exercise.

def _partner_device_id(partner_ref, mesh_ndim: int):
    """The partner's mesh-coordinate ``device_id`` tuple, read from the
    scalar-prefetch ref — the ONE encoding shared by the remote-copy
    descriptors and the credit-semaphore signals."""
    return tuple(partner_ref[a] for a in range(mesh_ndim))


def _rdma_descriptor(send_buf, recv_buf, send_sem, recv_sem, slot,
                     partner_ref, mesh_ndim: int):
    """The slot's remote-copy descriptor (reconstructed each iteration —
    start/wait are semaphore ops on the same (src, dst, sems, size)
    tuple)."""
    return pltpu.make_async_remote_copy(
        send_buf.at[slot], recv_buf.at[slot],
        send_sem.at[slot], recv_sem.at[slot],
        device_id=_partner_device_id(partner_ref, mesh_ndim),
        device_id_type=pltpu.DeviceIdType.MESH)


def _partner_barrier(partner_ref, mesh_ndim: int, i):
    """Entry handshake on the kernel's barrier semaphore (allocated by
    ``collective_id``): before the first remote copy, signal the partner
    and wait for its signal, so no block lands in VMEM recv slots of a
    partner that has not entered this kernel yet.  XOR pairing is
    symmetric, so each device receives exactly one signal."""
    @pl.when(i == 0)
    def _():
        sem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(sem, inc=1,
                               device_id=_partner_device_id(partner_ref,
                                                            mesh_ndim),
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(sem, 1)


def _slot_reuse_guard(rdma, cap_sem, slot, i):
    """Flow control before reusing slot ``i % 2`` at iteration ``i >= 2``:
    our own send from this slot must have drained AND the partner must
    have consumed the block we previously landed in ITS recv slot (one
    credit).  Shared by the forward and backward overlap kernels — the
    protocol must never desynchronize between them."""
    @pl.when(i >= 2)
    def _():
        rdma(slot).wait_send()
        pltpu.semaphore_wait(cap_sem, 1)


def _drain_epilogue(rdma, cap_sem, n_blocks: int):
    """Final-iteration drain: the last two sends were never waited on and
    the partner's last (up to two) credits never consumed — retire them
    so every semaphore ends the kernel at zero.  Shared by both overlap
    kernels."""
    rdma(jax.lax.rem(n_blocks - 1, 2)).wait_send()
    if n_blocks >= 2:
        rdma(jax.lax.rem(n_blocks - 2, 2)).wait_send()
    pltpu.semaphore_wait(cap_sem, min(2, n_blocks))


def _overlap_kernel(partner_ref, base_ref, *refs,
                    strides: Tuple[int, ...], n_blocks: int,
                    mesh_ndim: int, has_din: bool, has_dout: bool,
                    has_bias: bool, in_width: Optional[int],
                    quant_cf: bool = False):
    refs = list(refs)
    x_ref, cf_ref = refs.pop(0), refs.pop(0)
    scf_ref = refs.pop(0) if quant_cf else None
    ma_ref, mb_ref = refs.pop(0), refs.pop(0)
    din_ref = refs.pop(0) if has_din else None
    dout_ref = refs.pop(0) if has_dout else None
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, send_buf, recv_buf, send_sem, recv_sem, cap_sem = refs

    i = pl.program_id(0)
    _partner_barrier(partner_ref, mesh_ndim, i)

    def _rdma(slot):
        return _rdma_descriptor(send_buf, recv_buf, send_sem, recv_sem,
                                slot, partner_ref, mesh_ndim)

    @pl.when(i < n_blocks)
    def _compute_send():
        slot = jax.lax.rem(i, 2)
        _slot_reuse_guard(_rdma, cap_sem, slot, i)

        z = x_ref[...].astype(_F32)
        if in_width is not None:
            z = _mask_cols(z, base_ref[0], in_width)
        if has_din:
            z = z * din_ref[...].astype(_F32)
        z = _apply_stages_fwd(z, cf_ref, strides, scf_ref=scf_ref)
        send_buf[slot] = z.astype(send_buf.dtype)
        _rdma(slot).start()

    @pl.when(i > 0)
    def _recv_mix():
        slot = jax.lax.rem(i - 1, 2)
        _rdma(slot).wait_recv()
        zm = send_buf[slot].astype(_F32)
        zp = recv_buf[slot].astype(_F32)
        y = ma_ref[...].astype(_F32) * zm + mb_ref[...].astype(_F32) * zp
        if has_dout:
            # operator-boundary fold, scale-ON-STORE: d_out multiplies
            # the mixed result AFTER the add — bitwise the unfolded
            # post-stack elementwise op, which elastic re-sharding
            # depends on (see parallel/spm_shard._cross_mix)
            y = y * dout_ref[...].astype(_F32)
        if has_bias:
            y = y + bias_ref[...].astype(_F32)
        o_ref[...] = y.astype(o_ref.dtype)
        pltpu.semaphore_signal(cap_sem, inc=1,
                               device_id=_partner_device_id(partner_ref,
                                                            mesh_ndim),
                               device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(i == n_blocks)
    def _drain():
        _drain_epilogue(_rdma, cap_sem, n_blocks)


@functools.partial(jax.jit, static_argnames=("strides", "block_rows",
                                             "n_tile", "in_width",
                                             "collective_id", "interpret"))
def spm_overlap_kernel_call(x: jax.Array, coeffs: jax.Array,
                            mix_a: jax.Array, mix_b: jax.Array,
                            partner: jax.Array,
                            d_in: Optional[jax.Array] = None,
                            d_out: Optional[jax.Array] = None,
                            bias: Optional[jax.Array] = None,
                            col_base: Optional[jax.Array] = None,
                            coeff_scale: Optional[jax.Array] = None, *,
                            strides: Tuple[int, ...],
                            block_rows: int,
                            n_tile: int,
                            in_width: Optional[int] = None,
                            collective_id: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Fused {local run -> cross exchange -> mix epilogue} forward.

    x: (B, n_tile) shard slab — or, windowed (``col_base`` + ``in_width``,
    both GLOBAL as in ``spm_stack_kernel_call``), the feature-complete
    (B, in_width) operand.  coeffs: (L, n_tile//2, 4) local-run stages;
    mix_a / mix_b: (n_tile,) role-resolved cross coefficients
    (y = mix_a * z + mix_b * z_partner); partner: (mesh_ndim,) int32
    logical mesh coordinates of the XOR partner (scalar prefetch);
    optional d_in: (n_tile,) this shard's diagonal slice, folded before
    the first stage; optional d_out / bias: (n_tile,) this shard's
    output-boundary slices, applied by the mix epilogue when the schedule
    ENDS on this cross stage — d_out scales the mixed result AFTER the
    add (scale-on-store, bitwise the unfolded post-stack op) and bias
    follows.  Pipelines ``B // block_rows`` row blocks with
    double-buffered VMEM send/recv slots (budgeted by
    ``overlap_vmem_bytes``); returns the mixed (B, n_tile) slab.

    TPU-compile-only: ``make_async_remote_copy`` has no interpret
    realization (``core/eligibility.resolve_rdma`` gates engagement).
    """
    assert not interpret, "RDMA overlap kernel has no interpret mode"
    B = x.shape[0]
    L = coeffs.shape[0]
    assert 2 * coeffs.shape[1] == n_tile
    assert B % block_rows == 0
    nb = B // block_rows
    mesh_ndim = partner.shape[0]
    io_dt = x.dtype
    base = (col_base.astype(jnp.int32) if col_base is not None
            else jnp.zeros((1,), jnp.int32))

    nbm1 = nb - 1
    x_last = _last_block(x.shape[-1], n_tile)
    x_spec = pl.BlockSpec(
        (block_rows, n_tile),
        lambda i, p, b: (jnp.minimum(i, nbm1),
                         jnp.minimum(b[0], x_last) if in_width is not None
                         else 0))
    cf_spec = pl.BlockSpec((2 * L, n_tile), lambda i, p, b: (0, 0))
    vec_spec = pl.BlockSpec((1, n_tile), lambda i, p, b: (0, 0))
    o_spec = pl.BlockSpec((block_rows, n_tile),
                          lambda i, p, b: (jnp.maximum(i - 1, 0), 0))

    operands = [x, lane_major(coeffs, strides)]
    in_specs = [x_spec, cf_spec]
    if coeff_scale is not None:
        operands.append(coeff_scale.astype(jnp.float32).reshape(L, 1))
        in_specs.append(pl.BlockSpec((L, 1), lambda i, p, b: (0, 0)))
    operands += [mix_a.reshape(1, n_tile), mix_b.reshape(1, n_tile)]
    in_specs += [vec_spec, vec_spec]
    if d_in is not None:
        operands.append(d_in.reshape(1, n_tile))
        in_specs.append(vec_spec)
    if d_out is not None:
        operands.append(d_out.reshape(1, n_tile))
        in_specs.append(vec_spec)
    if bias is not None:
        operands.append(bias.reshape(1, n_tile))
        in_specs.append(vec_spec)

    kernel = functools.partial(_overlap_kernel, strides=strides,
                               n_blocks=nb, mesh_ndim=mesh_ndim,
                               has_din=d_in is not None,
                               has_dout=d_out is not None,
                               has_bias=bias is not None,
                               in_width=in_width,
                               quant_cf=coeff_scale is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb + 1,),
            in_specs=in_specs, out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((2, block_rows, n_tile), io_dt),   # send slots
                pltpu.VMEM((2, block_rows, n_tile), io_dt),   # recv slots
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,                  # credits
            ]),
        out_shape=jax.ShapeDtypeStruct((B, n_tile), io_dt),
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(partner.astype(jnp.int32), base, *operands)


def _overlap_bwd_kernel(partner_ref, base_ref, *refs,
                        strides: Tuple[int, ...], n_blocks: int,
                        mesh_ndim: int, has_din: bool, has_dout: bool,
                        in_width: Optional[int], quant_cf: bool = False):
    refs = list(refs)
    x_ref, xw_ref, cf_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    scf_ref = refs.pop(0) if quant_cf else None
    gy_ref = refs.pop(0)
    u_ref, v_ref = refs.pop(0), refs.pop(0)
    din_ref = refs.pop(0) if has_din else None
    # folded-boundary mode (schedule ends on this cross stage): the raw
    # gy streams through a SECOND walk-side window (block i-1, like x),
    # and this shard's d_out slab pre-scales the delta it SENDS
    gyw_ref = refs.pop(0) if has_dout else None
    dout_ref = refs.pop(0) if has_dout else None
    gx_ref, gcf_ref, gso_ref, gsw_ref = (refs.pop(0), refs.pop(0),
                                         refs.pop(0), refs.pop(0))
    gto_ref = refs.pop(0) if has_dout else None
    gtw_ref = refs.pop(0) if has_dout else None
    gdin_ref = refs.pop(0) if has_din else None
    send_buf, recv_buf, send_sem, recv_sem, cap_sem = refs

    i = pl.program_id(0)
    bb, nt = gy_ref.shape
    _partner_barrier(partner_ref, mesh_ndim, i)

    def _rdma(slot):
        return _rdma_descriptor(send_buf, recv_buf, send_sem, recv_sem,
                                slot, partner_ref, mesh_ndim)

    def _masked(xr):
        z = xr[...].astype(_F32)
        if in_width is not None:
            z = _mask_cols(z, base_ref[0], in_width)
        return z

    @pl.when(i < n_blocks)
    def _remat_send():
        slot = jax.lax.rem(i, 2)
        _slot_reuse_guard(_rdma, cap_sem, slot, i)

        z = _masked(x_ref)
        if has_din:
            z = z * din_ref[...].astype(_F32)
        z_out = _apply_stages_fwd(z, cf_ref, strides, scf_ref=scf_ref)
        if has_dout:
            # scale-before-exchange: each shard scales its OWN cotangent
            # by its OWN d_out slab, so the partner's delta arrives
            # correctly scaled without ever shipping the remote slab
            g = gy_ref[...].astype(_F32) * dout_ref[...].astype(_F32)
            send_buf[slot, 0] = g.astype(send_buf.dtype)
        else:
            send_buf[slot, 0] = gy_ref[...].astype(send_buf.dtype)
        send_buf[slot, 1] = z_out.astype(send_buf.dtype)
        _rdma(slot).start()

    @pl.when(i > 0)
    def _consume():
        slot = jax.lax.rem(i - 1, 2)
        _rdma(slot).wait_recv()
        delta = send_buf[slot, 0].astype(_F32)     # own block i-1 cotangent
        z_out = send_buf[slot, 1].astype(_F32)     # own remat z_out
        delta_p = recv_buf[slot, 0].astype(_F32)
        zp = recv_buf[slot, 1].astype(_F32)

        def _acc(ref, tile):
            @pl.when(i == 1)
            def _init():
                ref[...] = tile

            @pl.when(i > 1)
            def _add():
                ref[...] += tile

        # role-owned cross-coefficient sums (slot placement by the caller)
        _acc(gso_ref, jnp.sum(delta * z_out, axis=0).reshape(1, nt))
        _acc(gsw_ref, jnp.sum(delta * zp, axis=0).reshape(1, nt))
        if has_dout:
            # raw-cotangent sums for the folded d_out grad: g_dout =
            # mix_a*t_own + mix_b*t_swp outside the kernel (exact — no
            # division remat).  The packaged delta is pre-scaled, so the
            # raw gy comes from its own walk-side window.
            gy_raw = gyw_ref[...].astype(_F32)
            _acc(gto_ref, jnp.sum(gy_raw * z_out, axis=0).reshape(1, nt))
            _acc(gtw_ref, jnp.sum(gy_raw * zp, axis=0).reshape(1, nt))
        # transpose-mix prologue, then the local stage walk (collect remat)
        dmid = (u_ref[...].astype(_F32) * delta
                + v_ref[...].astype(_F32) * delta_p)
        x_raw = _masked(xw_ref)
        z0 = x_raw * din_ref[...].astype(_F32) if has_din else x_raw
        _, zs = _apply_stages_fwd(z0, cf_ref, strides, collect=True,
                                  scf_ref=scf_ref)
        delta0, g_rows = _stage_walk_bwd(zs, dmid, cf_ref, strides,
                                         scf_ref=scf_ref)
        _acc_rows(gcf_ref, g_rows, i == 1)
        if has_din:
            _acc(gdin_ref, jnp.sum(delta0 * x_raw, axis=0).reshape(1, nt))
            delta0 = delta0 * din_ref[...].astype(_F32)
        gx_ref[...] = delta0.astype(gx_ref.dtype)
        pltpu.semaphore_signal(cap_sem, inc=1,
                               device_id=_partner_device_id(partner_ref,
                                                            mesh_ndim),
                               device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(i == n_blocks)
    def _drain():
        _drain_epilogue(_rdma, cap_sem, n_blocks)


@functools.partial(jax.jit, static_argnames=("strides", "block_rows",
                                             "n_tile", "in_width",
                                             "collective_id", "interpret"))
def spm_overlap_bwd_kernel_call(x: jax.Array, coeffs: jax.Array,
                                gy: jax.Array,
                                u: jax.Array, v: jax.Array,
                                partner: jax.Array,
                                d_in: Optional[jax.Array] = None,
                                d_out: Optional[jax.Array] = None,
                                col_base: Optional[jax.Array] = None,
                                coeff_scale: Optional[jax.Array] = None, *,
                                strides: Tuple[int, ...],
                                block_rows: int,
                                n_tile: int,
                                in_width: Optional[int] = None,
                                collective_id: int = 1,
                                interpret: bool = False):
    """Fused backward of one {local run -> cross stage} pair from the
    LOCAL step's saved input.

    x: the local run's input — the (B, n_tile) slab, or the windowed
    feature-complete (B, in_width) operand (``col_base``); gy: (B, n_tile)
    post-cross cotangent slab; u / v: (n_tile,) role-resolved transpose
    mix (delta_mid = u * delta + v * delta_partner — (a, c) low,
    (d, b) high); partner: (mesh_ndim,) int32 mesh coordinates.  Each row
    block SENDS its (delta, remat z_out) package — the partner exchange
    is its own transpose — and the receiving iteration applies the
    transpose mix, accumulates the role-owned cross sums, and walks the
    local stages in reverse.

    Returns ``(g_x (B, n_tile), g_coeffs (L, n_tile//2, 4) f32,
    s_own (n_tile,), s_swp (n_tile,)[, g_din (n_tile,)]
    [, t_own (n_tile,), t_swp (n_tile,)])`` with
    s_own = sum_B delta * z_out and s_swp = sum_B delta * z_partner — the
    caller places them into the (a, b) / (c, d) slots by role.

    ``d_out`` engages the folded-boundary mode (the schedule ENDS on
    this cross stage — _pair_rdma_fwd folded d_out/bias into the mix
    epilogue): each block's SENT delta is pre-scaled by the shard's own
    d_out slab in VMEM (u/v stay the raw transpose-mix vectors), and two
    extra raw-cotangent sums t_own = sum_B gy * z_out / t_swp =
    sum_B gy * z_partner come back for the caller's exact
    ``g_dout = mix_a * t_own + mix_b * t_swp``.  TPU-only, like the
    forward."""
    assert not interpret, "RDMA overlap kernel has no interpret mode"
    B = gy.shape[0]
    L = coeffs.shape[0]
    assert 2 * coeffs.shape[1] == n_tile
    assert B % block_rows == 0
    nb = B // block_rows
    mesh_ndim = partner.shape[0]
    io_dt = gy.dtype
    base = (col_base.astype(jnp.int32) if col_base is not None
            else jnp.zeros((1,), jnp.int32))

    nbm1 = nb - 1
    x_last = _last_block(x.shape[-1], n_tile)
    x_col = ((lambda b: jnp.minimum(b[0], x_last)) if in_width is not None
             else (lambda b: 0))
    x_send_spec = pl.BlockSpec(
        (block_rows, n_tile),
        lambda i, p, b: (jnp.minimum(i, nbm1), x_col(b)))
    x_walk_spec = pl.BlockSpec(
        (block_rows, n_tile),
        lambda i, p, b: (jnp.maximum(i - 1, 0), x_col(b)))
    gy_spec = pl.BlockSpec((block_rows, n_tile),
                           lambda i, p, b: (jnp.minimum(i, nbm1), 0))
    cf_spec = pl.BlockSpec((2 * L, n_tile), lambda i, p, b: (0, 0))
    vec_spec = pl.BlockSpec((1, n_tile), lambda i, p, b: (0, 0))
    gx_spec = pl.BlockSpec((block_rows, n_tile),
                           lambda i, p, b: (jnp.maximum(i - 1, 0), 0))

    operands = [x, x, lane_major(coeffs, strides)]
    in_specs = [x_send_spec, x_walk_spec, cf_spec]
    if coeff_scale is not None:
        operands.append(coeff_scale.astype(jnp.float32).reshape(L, 1))
        in_specs.append(pl.BlockSpec((L, 1), lambda i, p, b: (0, 0)))
    operands += [gy, u.reshape(1, n_tile), v.reshape(1, n_tile)]
    in_specs += [gy_spec, vec_spec, vec_spec]
    if d_in is not None:
        operands.append(d_in.reshape(1, n_tile))
        in_specs.append(vec_spec)
    if d_out is not None:
        # folded-boundary mode: raw gy through a walk-side window
        # (block i-1, like x_walk_spec) + this shard's d_out slab
        gyw_spec = pl.BlockSpec((block_rows, n_tile),
                                lambda i, p, b: (jnp.maximum(i - 1, 0), 0))
        operands += [gy, d_out.reshape(1, n_tile)]
        in_specs += [gyw_spec, vec_spec]

    out_specs = [gx_spec, cf_spec, vec_spec, vec_spec]
    out_shape = [jax.ShapeDtypeStruct((B, n_tile), io_dt),
                 jax.ShapeDtypeStruct((2 * L, n_tile), jnp.float32),
                 jax.ShapeDtypeStruct((1, n_tile), jnp.float32),
                 jax.ShapeDtypeStruct((1, n_tile), jnp.float32)]
    if d_out is not None:
        out_specs += [vec_spec, vec_spec]          # t_own, t_swp
        out_shape += [jax.ShapeDtypeStruct((1, n_tile), jnp.float32),
                      jax.ShapeDtypeStruct((1, n_tile), jnp.float32)]
    if d_in is not None:
        out_specs.append(vec_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, n_tile), jnp.float32))

    kernel = functools.partial(_overlap_bwd_kernel, strides=strides,
                               n_blocks=nb, mesh_ndim=mesh_ndim,
                               has_din=d_in is not None,
                               has_dout=d_out is not None,
                               in_width=in_width,
                               quant_cf=coeff_scale is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb + 1,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, 2, block_rows, n_tile), io_dt),  # send slots
                pltpu.VMEM((2, 2, block_rows, n_tile), io_dt),  # recv slots
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,                    # credits
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(partner.astype(jnp.int32), base, *operands)
    gx, gcf, s_own, s_swp = (out[0], pair_table(out[1], strides), out[2],
                             out[3])
    res = (gx, gcf, s_own.reshape(n_tile), s_swp.reshape(n_tile))
    rest = list(out[4:])
    t_pair = ()
    if d_out is not None:
        t_pair = (rest.pop(0).reshape(n_tile), rest.pop(0).reshape(n_tile))
    if d_in is not None:
        res = res + (rest.pop(0).reshape(n_tile),)
    return res + t_pair
