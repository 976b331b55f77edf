"""Distributed two_level SPM: the feature axis sharded over ``"model"``.

The paper's two_level schedule was designed for exactly this executor
(core/pairings.py): with ``n = n_shards * n_local`` features block-sharded
over the mesh's ``"model"`` axis, every stage is one of two shapes:

* **shard-local run** (``n_local % (2*s) == 0``) — pairs stay inside one
  shard block.  Maximal consecutive runs of local stages execute on the
  shard-resident ``(rows, n_local)`` slab through the existing fused Pallas
  kernel (``kernels/spm_stack.py``; interpret mode off-TPU) or the XLA 2x2
  composition — zero communication.
* **cross-shard stage** (``s = k * n_local``, ``k`` a power of two) — pairs
  lane ``r`` of shard ``j`` with lane ``r`` of shard ``j XOR k``.  Realized
  as one ``jax.lax.ppermute`` partner exchange (an involution: the XOR
  permutation is its own inverse) plus a local 2x2 mix: the "low" partner
  (``j & k == 0``) holds the x0 role and computes ``y0 = a*x0 + b*x1``, the
  "high" partner computes ``y1 = c*x0 + d*x1``.

The whole sharded operator — D_in fold, stages, D_out/bias fold — runs
inside one ``shard_map`` with a closed-form ``custom_vjp``:

* the transpose of a partner exchange is the same exchange, so the backward
  walks the schedule in reverse issuing the SAME ppermutes (plus one for
  the saved stage input, needed by the coefficient grads);
* each shard computes only the coefficient-grad components its role owns
  (low: g_a, g_b; high: g_c, g_d) — the gather that built its coefficient
  table transposes to a scatter-add that merges the two partners' partials
  into the full (a, b, c, d) rows, so no all-reduce of parameter grads over
  the feature axis is ever issued;
* diag/bias grads are per-shard slices of (n,) vectors (out-sharded over
  ``"model"``), again collective-free.

Per-stage coefficient slabs are gathered OUTSIDE the shard_map
(``_step_tables`` — pure O(nL) indexing, differentiable) and passed in
pre-sharded with ``P("model")`` leading specs, so each device reads exactly
the rows its lanes need: for a local stage the contiguous pair block
``[j*n_local/2, (j+1)*n_local/2)``, for a cross stage the shared partner
rows ``[Q(j)*n_local, (Q(j)+1)*n_local)`` with
``Q(j) = ((j & ~k) // 2k)*k + ((j & ~k) % 2k)``.

The operator boundaries are kernel-native inside the shard (this PR):

* **diag/bias folding** — ``D_in`` folds into the first kernel run of the
  first shard-local step, ``D_out``/bias into the last kernel run of the
  last, exactly as the single-device plan folds them into its boundary
  runs — the shard body issues NO elementwise diag/bias ops (they only
  reappear on the XLA fallback path or when a boundary step is a
  cross-shard stage).  The boundary runs' backward kernels emit the
  closed-form g_din/g_dout/g_bias per-shard slices collective-free.
* **windowed rectangular boundaries** — for a rectangular operator the
  ``(rows, in_width)`` input enters the shard_map feature-REPLICATED and
  the first shard-local kernel run reads this shard's n_local-wide window
  straight out of it: a scalar-prefetch base tile offsets the x block
  index and an in-VMEM iota mask zero-fills lanes at or past the GLOBAL
  ``in_width`` (``kernels/spm_stack.py`` ``col_base``).  The zero-padded
  square input is never materialized in HBM and interior shards' masks
  are no-ops by construction.  The backward remats through the same
  windowed read (the replicated x is the residual) and the custom_vjp
  returns the input cotangent as ``(rows, in_width)`` with exact-zero
  padded-lane parameter grads.  The COTANGENT travels the other way: it
  enters the backward as an even-width slab (zero-padded to n — a local
  op fused into the slab reshard) rather than a windowed read, because
  replicating a feature-sharded cotangent would cost a
  batch-proportional all-gather.  Two further SPMD constraints remain by
  design: the assembled (rows, n) output is cut to ``out_width`` by one
  local per-shard slice (shard_map outputs must be evenly sharded), and
  the backward grid stays uniform across shards (a shard cannot skip its
  dead edge tiles — which costs no wall-clock, since the fully-live
  interior shards bound the step anyway).

The lowered HLO of this path contains ``collective-permute`` only — no
all-gather or all-reduce of the feature axis (asserted by
tests/test_distributed.py via ``hlo_analysis.collective_bytes``; the
backward's two bounded exceptions are the O(nL) replicated
coefficient-grad assembly and, for rectangular operators only, the
jit-boundary replication of the indivisible-width g_x output — inherent
to any transport design).

**Overlap schedule** (this PR): with ``SPMConfig.overlap`` resolved on
(``core/eligibility.resolve_overlap`` — auto on TPU, forceable
everywhere), the walk above restructures into a row-block pipeline: the
slab splits into ``ShardPlan.row_blocks`` and every step processes
per block, so block i's partner exchange flies while block i+1 computes.
On compiled TPU backends each {local run -> cross stage} pair fuses into
ONE pallas_call (``kernels/spm_stack.spm_overlap_kernel_call`` — the
remote copy is an in-kernel ``pltpu.make_async_remote_copy`` started per
row block, the 2x2 mix its receiving epilogue, and the backward remats
the sent activation in VMEM; those cross steps save placeholder
residuals, ``ShardPlan.rdma_crosses``).  Everywhere else the SAME
schedule transports blocks via per-block ``jax.lax.ppermute`` — the
interpret-mode proof path — and the custom_vjp replays the overlapped
walk in reverse using the same exchange-is-its-own-transpose property.
``launch/hlo_analysis.sharded_stage_traffic(..., overlap=True)`` models
the exposed-vs-hidden permute-byte split; docs/sharding.md "The overlap
executor" is the design reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import spm as spm_mod
from repro.core.eligibility import (OVERLAP_ROW_BLOCKS, overlap_segments,
                                    plan_steps, resolve_overlap,
                                    resolve_rdma, resolve_shard_kernel,
                                    sharded_eligible)
from repro.core.pairings import Stage
from repro.kernels import quant as Q
from repro.kernels import spm_stack as K
from repro.kernels.ops import (default_interpret, pick_block_rows_for_plan,
                               plan_runs)

__all__ = ["spm_apply_sharded", "sharded_eligible", "plan_steps",
           "cross_partner_perm", "pick_row_blocks"]

AXIS = "model"
_F32 = jnp.float32

# plan_steps / sharded_eligible / OVERLAP_ROW_BLOCKS moved to
# core/eligibility.py (the single
# fallback matrix shared with the single-device kernel path); re-exported
# here unchanged for back-compat.


def cross_partner_perm(n_shards: int, k: int) -> Tuple[Tuple[int, int], ...]:
    """The ppermute permutation of a cross stage: shard j <-> j XOR k.
    An involution — forward and backward issue the identical exchange."""
    return tuple((j, j ^ k) for j in range(n_shards))


@functools.lru_cache(maxsize=None)
def _cross_coeff_rows(n_shards: int, n_local: int, k: int) -> np.ndarray:
    """(n_shards, n_local) pair-row indices for a cross stage: lane r of
    shard j (and of its partner j XOR k — the rows are shared) uses pair
    Q(j)*n_local + r with Q(j) = ((j & ~k) // 2k)*k + ((j & ~k) % 2k)."""
    j = np.arange(n_shards)
    jl = j & ~k                       # the pair's low-partner shard id
    q = (jl // (2 * k)) * k + (jl % (2 * k))
    return q[:, None] * n_local + np.arange(n_local)[None, :]


# ---------------------------------------------------------------------------
# static plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Hashable static description closed over by the custom_vjp.

    ``in_width`` / ``out_width`` are the GLOBAL rectangular widths (None =
    square).  The derived ``win_in`` flag says whether the first
    shard-local kernel run reads the input through a windowed
    (scalar-prefetch offset) kernel call; ``fold_din`` / ``fold_dout`` /
    ``fold_bias`` say whether the diag/bias operands fold into the
    boundary kernel runs instead of running as elementwise ops in the
    shard body.
    """

    mesh: Mesh
    n: int
    n_local: int
    n_shards: int
    steps: Tuple[tuple, ...]
    has_din: bool
    has_dout: bool
    has_bias: bool
    use_kernel: bool
    block_rows: int
    interpret: bool
    dp: Tuple[str, ...] = ()     # pure-DP mesh axes: rows shard over these
    in_width: Optional[int] = None
    out_width: Optional[int] = None
    # -- overlap schedule (this PR) ----------------------------------------
    # row_blocks: static per-shard row-block sizes of the pipelined walk
    # (empty = step-serial full-slab schedule).  rdma_crosses: indices of
    # cross steps executed as the epilogue of a fused RDMA pair kernel
    # (TPU only — see core/eligibility.resolve_rdma); their saved stage
    # input is a placeholder, rematerialized in VMEM by the backward
    # kernel.
    row_blocks: Tuple[int, ...] = ()
    rdma_crosses: Tuple[int, ...] = ()
    # quant_cf: shard-local kernel runs read int8 per-stage-scaled
    # coefficient tables, dequantized in VMEM (SPMConfig.quant_coeffs).
    # The quantization is recomputed deterministically from the f32 slab
    # in forward AND backward, so both see identical dequantized values
    # and the closed-form grads are grads of the dequantized operator
    # (straight-through in the table params).  Cross-stage 2x2 mixes are
    # O(n) elementwise XLA ops and stay f32.
    quant_cf: bool = False

    @property
    def overlap(self) -> bool:
        """Whether the row-block pipelined (overlap) walk is engaged."""
        return bool(self.row_blocks)

    @property
    def segments(self) -> Tuple[tuple, ...]:
        """The overlap segmentation of ``steps`` (``("pair", local,
        cross)`` / ``("one", step)`` — core/eligibility.overlap_segments)."""
        return overlap_segments(self.steps)

    # -- boundary-step structure -------------------------------------------
    @property
    def first_local(self) -> bool:
        return self.steps[0][0] == "local"

    @property
    def last_local(self) -> bool:
        return self.steps[-1][0] == "local"

    @property
    def fold_din(self) -> bool:
        """D_in folds into the first kernel run of the first local step."""
        return self.has_din and self.use_kernel and self.first_local

    @property
    def fold_dout(self) -> bool:
        """D_out folds into the schedule's last step: into the last kernel
        run when the schedule ends on a local step, or — when it ends on a
        CROSS stage — into the mix epilogue itself, scaling the mixed
        result ON THE STORE (after the mix add, bitwise the unfolded
        post-stack op — elastic re-sharding depends on that order; on the
        RDMA path the kernel's receive-mix applies it as one extra vector
        operand, so the slab never round-trips HBM for the boundary).
        Only a kernel-off local ending still applies d_out as an explicit
        batch-wide elementwise op."""
        return self.has_dout and (self.use_kernel if self.last_local
                                  else True)

    @property
    def fold_bias(self) -> bool:
        """Bias folds exactly like ``fold_dout`` (one fused add in the mix
        epilogue on a cross ending)."""
        return self.has_bias and (self.use_kernel if self.last_local
                                  else True)

    @property
    def win_in(self) -> bool:
        """The first kernel run reads the (rows, in_width) global input
        through a windowed (col_base) call — the padded square input is
        never materialized in HBM."""
        return (self.in_width is not None and self.use_kernel
                and self.first_local)

    # NOTE deliberately no ``win_out``: the backward cotangent is
    # transported as an even-width slab (zero-padded to n in
    # ``_sharded_core_bwd`` — a local op fused into the slab reshard)
    # rather than window-read from a replicated (rows, out_width) array.
    # The windowed read would force replicating the cotangent, and when it
    # arrives feature-sharded (the common case: it flows back from the
    # sharded forward output) that replication is a batch-proportional
    # all-gather over ICI — strictly worse than the fused local pad.

    # -- residual layout ----------------------------------------------------
    @property
    def saves_x_res(self) -> bool:
        """Whether a stage-0 input residual rides next to step_ins: the
        replicated x itself under win_in (the backward's windowed remat
        source), else the pre-D_in slab when g_din is computed explicitly."""
        return self.win_in or (self.has_din and not self.fold_din)

    @property
    def saves_z_last(self) -> bool:
        """z_L (pre-D_out) is a residual only when g_dout is explicit; a
        folded boundary run remats it in VMEM."""
        return self.has_dout and not self.fold_dout

    # -- shard_map specs ----------------------------------------------------
    def table_specs(self) -> Tuple[P, ...]:
        return tuple(P(AXIS) for _ in self.steps)

    def vec_spec(self, present: bool) -> P:
        return P(AXIS) if present else P()   # (1,) placeholders replicated

    def act_spec(self) -> P:
        # (rows, n): rows over the DP axes (kept replicated when there are
        # none), features over "model" — entering with batch-sharded
        # activations must NOT all-gather them.
        return P(self.dp if self.dp else None, AXIS)

    def rep_spec(self) -> P:
        # (rows, width) with the feature axis replicated over "model" —
        # the natural sharding of a rectangular boundary operand, whose
        # width is not divisible by the shard count.
        return P(self.dp if self.dp else None, None)

    def x_spec(self) -> P:
        return self.rep_spec() if self.in_width is not None \
            else self.act_spec()

    def res_specs(self):
        """Shard_map specs of the residual tuple ``(x_res, step_ins,
        z_last)``: placeholders ride replicated ``P(None)``, slabs the act
        spec, the windowed x residual the replicated rep spec.  An RDMA
        pair's cross step saves a placeholder — its stage input (the local
        run's output) never reaches HBM and the backward kernel remats it
        from the local run's own input."""
        act = self.act_spec()
        x_res = (self.rep_spec() if self.win_in
                 else (act if self.saves_x_res else P(None)))
        step_ins = tuple(P(None) if ((i == 0 and self.win_in)
                                     or i in self.rdma_crosses) else act
                         for i in range(len(self.steps)))
        z_last = act if self.saves_z_last else P(None)
        return (x_res, step_ins, z_last)


def _step_tables(coeffs: jax.Array, steps, n_shards: int,
                 n_local: int) -> Tuple[jax.Array, ...]:
    """Per-step coefficient tables with a leading shard axis (sharded into
    the shard_map with P("model")).  Differentiable: the local case is a
    reshape/transpose, the cross case a gather whose transpose scatter-adds
    the two partners' grad partials into the shared rows."""
    nl2 = n_local // 2
    tabs = []
    for step in steps:
        if step[0] == "local":
            _, start, run = step
            blk = coeffs[start: start + len(run)]          # (Lr, n/2, 4)
            tabs.append(blk.reshape(len(run), n_shards, nl2, 4)
                        .transpose(1, 0, 2, 3))            # (S, Lr, nl2, 4)
        else:
            _, ell, k = step
            rows = _cross_coeff_rows(n_shards, n_local, k)
            tabs.append(coeffs[ell][rows])                 # (S, n_local, 4)
    return tuple(tabs)


def _window_slab(x_full: jax.Array, base_cols: jax.Array, n_local: int,
                 width: int) -> jax.Array:
    """XLA fallback for the windowed boundary read: this shard's
    (rows, n_local) slab of a feature-complete (rows, width) operand,
    zero-filled past ``width``.  A clipped static-length gather + mask —
    local, collective-free, but it does materialize the slab in HBM,
    which the windowed KERNEL read (``win_in``) avoids."""
    col = base_cols + jnp.arange(n_local)
    idx = jnp.clip(col, 0, width - 1)
    slab = jnp.take(x_full, idx, axis=-1)
    return jnp.where(col < width, slab, jnp.zeros_like(slab))


# ---------------------------------------------------------------------------
# shard-local stage math
# ---------------------------------------------------------------------------

def _cross_mix(z, zp, cf, k: int, d_out=None, bias=None):
    """The local 2x2 half of a cross stage, once the partner slab ``zp``
    is in hand: the low partner (``j & k == 0``) holds the x0 role and
    computes ``y0 = a*z + b*zp``, the high partner ``y1 = c*zp + d*z``.
    The OPERAND ORDER of each two-term form is load-bearing: XLA
    contracts ``p*q + r`` into an fma whose rounding depends on which
    product stays exact, and an elastic execution classifies this same
    pinned stage LOCAL on a wider-``n_local`` mesh — where the pair math
    computes exactly these forms — so any re-association here breaks
    bitwise re-shard parity.  When the schedule ENDS on this stage the
    operator boundary folds in ON THE STORE: ``d_out`` scales the mixed
    result AFTER the add (never pre-scaled into the mix coefficients, for
    the same bitwise reason) and ``bias`` rides the same fused region.
    Factored out of ``_cross_fwd`` so the overlap schedule can apply it
    per row block (and the RDMA kernel as its in-VMEM epilogue, with the
    same scale-on-store order)."""
    low = (jax.lax.axis_index(AXIS) & k) == 0
    a, b, c, d = (cf[:, i].astype(z.dtype) for i in range(4))
    y = jnp.where(low, a * z + b * zp, c * zp + d * z)
    if d_out is not None:
        y = y * d_out.astype(z.dtype)
    if bias is not None:
        y = y + bias.astype(z.dtype)
    return y


def _cross_fwd(z, cf, k: int, plan: ShardPlan, d_out=None, bias=None):
    """One partner exchange + local 2x2 mix.  z: (rows, n_local);
    cf: (n_local, 4) rows shared with the partner shard.  ``d_out`` /
    ``bias`` fold the operator boundary into the mix (schedule-ending
    cross stage — see ``_cross_mix``)."""
    zp = jax.lax.ppermute(z, AXIS, cross_partner_perm(plan.n_shards, k))
    return _cross_mix(z, zp, cf, k, d_out=d_out, bias=bias)


def _cross_bwd(z_in, delta, cf, k: int, plan: ShardPlan,
               d_out=None, has_bias: bool = False):
    """Transpose of the partner exchange is the same exchange.  Each shard
    emits only the coefficient-grad components its role owns (low: a, b;
    high: c, d); the table gather's scatter-add merges the partners.

    With ``d_out`` (folded boundary — this cross stage ended the
    schedule), ``delta`` arrives RAW (the output cotangent): ``g_bias``
    sums it as-is, ``g_dout`` contracts it against the rematerialized mix
    output ``u*z + v*zp`` (no stored pre-d_out activation needed), and the
    mix cotangent is ``d_out * delta`` — scaled by the shard's OWN d_out
    slice BEFORE the partner exchange, so the partner's arrives pre-scaled
    by ITS slice.  Returns ``(g_in, g_cf, extras)`` with extras ordered
    [g_dout?, g_bias?]."""
    perm = cross_partner_perm(plan.n_shards, k)
    zp = jax.lax.ppermute(z_in, AXIS, perm)
    low = (jax.lax.axis_index(AXIS) & k) == 0
    extras = []
    if d_out is not None or has_bias:
        if has_bias:
            g_bias = jnp.sum(delta.astype(_F32), axis=0)
        if d_out is not None:
            # remat the mix output in the forward's exact operand order
            # (see _cross_mix — the two-sided form is the bitwise anchor)
            af, bf, cf_, df = (cf[:, i].astype(_F32) for i in range(4))
            zf, zpf = z_in.astype(_F32), zp.astype(_F32)
            m = jnp.where(low, af * zf + bf * zpf, cf_ * zpf + df * zf)
            extras.append(jnp.sum(delta.astype(_F32) * m, axis=0))
            delta = delta * d_out.astype(delta.dtype)
        if has_bias:
            extras.append(g_bias)
    dp = jax.lax.ppermute(delta, AXIS, perm)
    a, b, c, d = (cf[:, i].astype(delta.dtype) for i in range(4))
    # g_x0 = a d0 + c d1 on the low shard; g_x1 = b d0 + d d1 on the high.
    g_in = jnp.where(low, a * delta + c * dp, b * dp + d * delta)
    # low holds (d0, x0) and receives x1=zp: g_a = sum d0 x0, g_b = sum d0 x1
    # high holds (d1, x1) and receives x0=zp: g_c = sum d1 x0, g_d = sum d1 x1
    s_own = jnp.sum(delta.astype(_F32) * z_in.astype(_F32), axis=0)
    s_swp = jnp.sum(delta.astype(_F32) * zp.astype(_F32), axis=0)
    zero = jnp.zeros_like(s_own)
    g_cf = jnp.where(low,
                     jnp.stack([s_own, s_swp, zero, zero], axis=-1),
                     jnp.stack([zero, zero, s_swp, s_own], axis=-1))
    return g_in, g_cf.astype(cf.dtype), extras


def _base_tiles(col_base, n_tile: int):
    """Convert a traced base-column scalar to the (1,) base-feature-tile
    operand of a windowed kernel call."""
    return jnp.reshape(col_base // n_tile, (1,))


def _segment_fwd(z, cf, run: Tuple[int, ...], plan: ShardPlan, *,
                 d_in=None, d_out=None, bias=None,
                 col_base=None, in_width: Optional[int] = None):
    """A maximal run of shard-local stages on the resident slab: the fused
    Pallas kernel when enabled (interpret off-TPU), else the XLA 2x2
    composition.  On the kernel path the BOUNDARY sub-runs absorb the
    operator boundaries: ``d_in`` folds into the first sub-run (applied in
    VMEM before its first stage), ``d_out``/``bias`` into the last, and
    with ``col_base``/``in_width`` the first sub-run is a windowed call
    that reads this shard's n_local-wide window straight out of the
    feature-complete (rows, in_width) operand ``z``."""
    if plan.use_kernel:
        runs = plan_runs(plan.n_local, run)
        kcf, scf = (Q.quantize_coeffs(cf) if plan.quant_cf
                    else (cf, None))
        off = 0
        for r, (run_strides, n_tile) in enumerate(runs):
            first, last = r == 0, r == len(runs) - 1
            z = K.spm_stack_kernel_call(
                z, kcf[off: off + len(run_strides)],
                d_in if first else None,
                d_out if last else None,
                bias if last else None,
                _base_tiles(col_base, n_tile)
                if (first and col_base is not None) else None,
                coeff_scale=(scf[off: off + len(run_strides)]
                             if plan.quant_cf else None),
                strides=run_strides, block_rows=plan.block_rows,
                n_tile=n_tile,
                in_width=in_width if first else None,
                interpret=plan.interpret)
            off += len(run_strides)
        return z
    for i, s in enumerate(run):
        z = spm_mod.apply_stage(z, cf[i].astype(z.dtype), Stage(stride=s))
    return z


def _segment_bwd(z_in, delta, cf, run: Tuple[int, ...], plan: ShardPlan, *,
                 d_in=None, d_out=None, has_bias: bool = False,
                 col_base=None, in_width: Optional[int] = None):
    """Closed-form backward of a local run from its saved input: the fused
    backward kernel per planned sub-run (stage inputs remat in VMEM), else
    forward-recompute + per-stage eq. 12-14 grads.

    Kernel path boundary handling mirrors ``_segment_fwd``: the first
    sub-run consumes ``d_in`` (and with ``in_width``/``col_base`` remats
    from the feature-complete replicated x through a windowed read,
    emitting exact-zero padded-lane grads), the last sub-run consumes
    ``d_out``/``has_bias``.  ``delta`` is always the slab cotangent (a
    rectangular out_width arrives pre-zero-padded — see _shard_bwd).
    Returns ``(delta_slab, g_coeffs, vec_grads)`` with ``vec_grads``
    ordered [g_din?, g_dout?, g_bias?].
    """
    if plan.use_kernel:
        runs = plan_runs(plan.n_local, run)
        # recompute the SAME deterministic quantization as the forward so
        # the remat and the grads see identical dequantized tables
        kcf, scf = (Q.quantize_coeffs(cf) if plan.quant_cf
                    else (cf, None))
        zs, z, off = [], z_in, 0
        for r, (run_strides, n_tile) in enumerate(runs):
            zs.append(z)
            if r < len(runs) - 1:    # the last output is never needed
                z = K.spm_stack_kernel_call(
                    z, kcf[off: off + len(run_strides)],
                    d_in if r == 0 else None, None, None,
                    _base_tiles(col_base, n_tile)
                    if (r == 0 and in_width is not None
                        and col_base is not None) else None,
                    coeff_scale=(scf[off: off + len(run_strides)]
                                 if plan.quant_cf else None),
                    strides=run_strides, block_rows=plan.block_rows,
                    n_tile=n_tile,
                    in_width=in_width if r == 0 else None,
                    interpret=plan.interpret)
            off += len(run_strides)
        offs = np.cumsum([0] + [len(rs) for rs, _ in runs])
        g_parts = [None] * len(runs)
        g_din = g_dout = g_bias = None
        for r in range(len(runs) - 1, -1, -1):
            run_strides, n_tile = runs[r]
            first, last = r == 0, r == len(runs) - 1
            win_x = first and in_width is not None and col_base is not None
            out = K.spm_stack_bwd_kernel_call(
                zs[r], kcf[offs[r]: offs[r + 1]], delta,
                d_in if first else None,
                d_out if last else None,
                _base_tiles(col_base, n_tile) if win_x else None,
                coeff_scale=(scf[offs[r]: offs[r + 1]]
                             if plan.quant_cf else None),
                strides=run_strides, block_rows=plan.block_rows,
                n_tile=n_tile, has_bias=last and has_bias,
                in_width=in_width if first else None,
                interpret=plan.interpret)
            delta, g_parts[r] = out[0], out[1]
            vecs = list(out[2:])
            if first and d_in is not None:
                g_din = vecs.pop(0)
            if last and d_out is not None:
                g_dout = vecs.pop(0)
            if last and has_bias:
                g_bias = vecs.pop(0)
        vec_grads = [g for g in (g_din, g_dout, g_bias) if g is not None]
        return (delta, jnp.concatenate(g_parts, axis=0).astype(cf.dtype),
                vec_grads)
    zs, z = [], z_in
    for i, s in enumerate(run):
        zs.append(z)
        if i < len(run) - 1:
            z = spm_mod.apply_stage(z, cf[i].astype(z.dtype),
                                    Stage(stride=s))
    g_cf = []
    for i in range(len(run) - 1, -1, -1):
        delta, gc, _ = spm_mod._stage_grads(
            zs[i], delta, cf[i].astype(delta.dtype), Stage(stride=run[i]),
            None)
        g_cf.append(gc)
    return delta, jnp.stack(g_cf[::-1], axis=0).astype(cf.dtype), []


# ---------------------------------------------------------------------------
# overlap schedule: row-block pipelined walk
# ---------------------------------------------------------------------------

def pick_row_blocks(rows: int, block_rows: int,
                    target: int = OVERLAP_ROW_BLOCKS) -> Tuple[int, ...]:
    """Static per-shard row-block sizes of the overlap pipeline.

    Splits ``rows`` (the per-DP-shard slab rows, already padded to a
    ``block_rows`` multiple) into at most ``target`` contiguous blocks,
    each a ``block_rows`` multiple so every block is a whole number of
    kernel row-blocks.  Degenerate inputs (fewer kernel row-blocks than
    ``target``) get fewer, down to the single-block tuple — the overlap
    walk then reduces to the step-serial schedule on the same code path.
    """
    if rows <= 0:
        return (max(rows, 0),) if rows else ()
    units = max(1, rows // block_rows)        # whole kernel row-blocks
    nb = max(1, min(target, units))
    base, extra = divmod(units, nb)
    sizes = []
    used = 0
    for b in range(nb):
        u = base + (1 if b < extra else 0)
        sizes.append(u * block_rows)
        used += u * block_rows
    sizes[-1] += rows - used                  # fold any sub-block remainder
    return tuple(s for s in sizes if s > 0)


def _overlap_split(z, row_blocks: Tuple[int, ...]):
    """Slice the slab's row axis into the plan's static row blocks."""
    offs = np.cumsum((0,) + row_blocks)
    return [jax.lax.slice_in_dim(z, int(offs[b]), int(offs[b + 1]), axis=0)
            for b in range(len(row_blocks))]


def _partner_coords(plan: ShardPlan, k: int):
    """(mesh.ndim,) int32 logical mesh coordinates of this shard's XOR-k
    partner — every axis keeps this device's index except ``"model"``,
    which flips to ``j XOR k``.  Consumed by the RDMA kernels' remote-copy
    ``device_id`` (scalar prefetch)."""
    coords = []
    for a in plan.mesh.axis_names:
        idx = jax.lax.axis_index(a)
        if a == AXIS:
            idx = idx ^ k
        coords.append(idx)
    return jnp.stack([c.astype(jnp.int32) for c in coords])


def _cross_role_vecs(cf, k: int, low):
    """Role-resolved forward mix vectors: the epilogue computes
    ``y = mix_a * z + mix_b * zp`` where (mix_a, mix_b) is (a, b) on the
    low partner and (d, c) on the high — O(n_local) elementwise, computed
    in the shard body so the kernel itself is role-free."""
    return (jnp.where(low, cf[:, 0], cf[:, 3]),
            jnp.where(low, cf[:, 1], cf[:, 2]))


def _pair_rdma_fwd(z, li: int, ci: int, plan: ShardPlan, tabs,
                   d_in, d_out, bias, base_cols):
    """One fused {local run -> cross exchange -> mix epilogue} pallas_call
    over the whole slab: the kernel row-block-pipelines internally, a
    block's partner-half remote copy starting as soon as its local mix
    finishes (kernels/spm_stack.spm_overlap_kernel_call).  When this pair's
    cross stage ENDS the schedule, the operator boundary folds into the
    receive-mix epilogue as two extra vector operands: ``d_out`` scales
    the mixed result AFTER the add (scale-on-store — bitwise the unfolded
    post-stack op, which elastic re-sharding depends on) and ``bias``
    rides the same store."""
    local_step, cross_step = plan.steps[li], plan.steps[ci]
    k = cross_step[2]
    low = (jax.lax.axis_index(AXIS) & k) == 0
    mix_a, mix_b = _cross_role_vecs(tabs[ci][0], k, low)
    last = ci == len(plan.steps) - 1
    (run_strides, n_tile), = plan_runs(plan.n_local, local_step[2])
    first = li == 0
    kcf, scf = (Q.quantize_coeffs(tabs[li][0]) if plan.quant_cf
                else (tabs[li][0], None))
    return K.spm_overlap_kernel_call(
        z, kcf, mix_a, mix_b, _partner_coords(plan, k),
        d_in=d_in if (first and plan.fold_din) else None,
        d_out=d_out if (last and plan.fold_dout) else None,
        bias=bias if (last and plan.fold_bias) else None,
        col_base=(_base_tiles(base_cols, n_tile)
                  if (first and plan.win_in) else None),
        coeff_scale=scf,
        strides=run_strides, block_rows=plan.block_rows, n_tile=n_tile,
        in_width=plan.in_width if (first and plan.win_in) else None,
        collective_id=2 * ci)       # distinct per pair; bwd takes 2*ci+1


def _pair_rdma_bwd(z_in, delta, li: int, ci: int, plan: ShardPlan, tabs,
                   d_in, d_out, base_cols):
    """Backward of an RDMA pair from the LOCAL step's saved input: the
    kernel remats the local run's output in VMEM (the forward sent it
    without ever writing HBM), exchanges (delta, z_out) blocks with the
    partner — the partner exchange is its own transpose — applies the
    cross-backward mix as its prologue and walks the local stages in
    reverse.  Returns (delta, g_local_coeffs, g_cross_coeffs, vec_grads)
    with the cross grads placed into the role-owned (a,b)/(c,d) slots
    exactly as ``_cross_bwd`` does and ``vec_grads`` ordered
    [g_din?, g_dout?, g_bias?].

    When this pair's cross stage ENDED the schedule with a folded
    boundary, ``delta`` arrives RAW: ``g_bias`` sums it in the shard body,
    the kernel pre-scales each SENT block by the shard's own d_out slice
    and returns the raw-cotangent sums (t_own, t_swp), and
    ``g_dout = mix_a * t_own + mix_b * t_swp`` with the UNSCALED forward
    role vectors — exact, no division remat."""
    local_step, cross_step = plan.steps[li], plan.steps[ci]
    k = cross_step[2]
    low = (jax.lax.axis_index(AXIS) & k) == 0
    cfc = tabs[ci][0]
    # transpose mix: g_mid = u * delta + v * delta_p with (u, v) = (a, c)
    # on the low partner and (d, b) on the high (see _cross_bwd)
    u = jnp.where(low, cfc[:, 0], cfc[:, 3])
    v = jnp.where(low, cfc[:, 2], cfc[:, 1])
    (run_strides, n_tile), = plan_runs(plan.n_local, local_step[2])
    first = li == 0
    last = ci == len(plan.steps) - 1
    fold_dout = last and plan.fold_dout
    kcf, scf = (Q.quantize_coeffs(tabs[li][0]) if plan.quant_cf
                else (tabs[li][0], None))
    out = K.spm_overlap_bwd_kernel_call(
        z_in, kcf, delta, u, v, _partner_coords(plan, k),
        d_in=d_in if (first and plan.fold_din) else None,
        d_out=d_out if fold_dout else None,
        col_base=(_base_tiles(base_cols, n_tile)
                  if (first and plan.win_in) else None),
        coeff_scale=scf,
        strides=run_strides, block_rows=plan.block_rows, n_tile=n_tile,
        in_width=plan.in_width if (first and plan.win_in) else None,
        collective_id=2 * ci + 1)
    gx, g_local, s_own, s_swp = out[:4]
    vecs = list(out[4:])           # [g_din?] + [t_own, t_swp]?
    if fold_dout:
        t_swp = vecs.pop()
        t_own = vecs.pop()
        mix_a, mix_b = _cross_role_vecs(cfc, k, low)
        vecs.append(mix_a.astype(_F32) * t_own
                    + mix_b.astype(_F32) * t_swp)
    if last and plan.fold_bias:
        vecs.append(jnp.sum(delta.astype(_F32), axis=0))
    delta = gx
    zero = jnp.zeros_like(s_own)
    g_cross = jnp.where(low,
                        jnp.stack([s_own, s_swp, zero, zero], axis=-1),
                        jnp.stack([zero, zero, s_swp, s_own], axis=-1))
    return (delta, g_local.astype(tabs[li][0].dtype),
            g_cross.astype(cfc.dtype), vecs)


def _overlap_steps_fwd(plan: ShardPlan, tabs, d_in, d_out, bias, z,
                       base_cols, collect: bool):
    """Row-block pipelined forward walk of the schedule.

    Blocks are independent, so issuing block b's partner exchange right
    after its local mix lets it fly while block b+1 computes — on TPU the
    pair segments fuse this into one RDMA kernel
    (``plan.rdma_crosses``); everywhere else the per-block
    ``jax.lax.ppermute`` transport realizes the IDENTICAL schedule (the
    interpret-mode proof path), with XLA's async collectives free to
    overlap the in-flight permutes with the next block's kernel.
    Residual layout matches the serial walk except RDMA cross steps,
    whose stage input is a placeholder (rematerialized by the backward
    kernel)."""
    fdt = z.dtype
    ph = jnp.zeros((1,), fdt)
    n_steps = len(plan.steps)
    step_ins = [ph] * n_steps
    i = 0
    for seg in plan.segments:
        if seg[0] == "pair" and (i + 1) in plan.rdma_crosses:
            li, ci = i, i + 1
            if collect and not (li == 0 and plan.win_in):
                step_ins[li] = z
            z = _pair_rdma_fwd(z, li, ci, plan, tabs, d_in, d_out, bias,
                               base_cols)
            i += 2
            continue
        for step in (seg[1:] if seg[0] == "pair" else (seg[1],)):
            first, last = i == 0, i == n_steps - 1
            if collect and not (first and plan.win_in):
                step_ins[i] = z
            cf = tabs[i][0]
            blocks = _overlap_split(z, plan.row_blocks)
            if step[0] == "cross":
                perm = cross_partner_perm(plan.n_shards, step[2])
                zps = [jax.lax.ppermute(b, AXIS, perm) for b in blocks]
                outs = [_cross_mix(
                    b, p, cf, step[2],
                    d_out=d_out if (last and plan.fold_dout) else None,
                    bias=bias if (last and plan.fold_bias) else None)
                    for b, p in zip(blocks, zps)]
            else:
                outs = [_segment_fwd(
                    b, cf, step[2], plan,
                    d_in=d_in if (first and plan.fold_din) else None,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    bias=bias if (last and plan.fold_bias) else None,
                    col_base=base_cols if (first and plan.win_in) else None,
                    in_width=plan.in_width
                    if (first and plan.win_in) else None) for b in blocks]
            z = jnp.concatenate(outs, axis=0)
            i += 1
    return z, step_ins


def _sum_vec_lists(parts):
    """Elementwise-sum the per-block ``vec_grads`` lists of a local step
    (each ordered [g_din?, g_dout?, g_bias?])."""
    if not parts or not parts[0]:
        return []
    return [functools.reduce(jnp.add, [p[j] for p in parts])
            for j in range(len(parts[0]))]


def _overlap_steps_bwd(plan: ShardPlan, tabs, d_in, d_out, res, delta,
                       base_cols):
    """Reverse of ``_overlap_steps_fwd``: walks the segments backwards,
    per row block, replaying the same exchanges (the XOR permutation is
    its own transpose); RDMA pairs run their fused backward kernel on the
    whole slab.  Returns (delta, g_tabs in schedule order, vec_grads dict
    keyed 'din'/'dout'/'bias' for the folded boundary grads)."""
    x_res, step_ins, _ = res
    n_steps = len(plan.steps)
    g_tabs = [None] * n_steps
    folded = {}
    spans = []
    i = 0
    for seg in plan.segments:
        spans.append((seg, i))
        i += 2 if seg[0] == "pair" else 1
    for seg, i0 in reversed(spans):
        if seg[0] == "pair" and (i0 + 1) in plan.rdma_crosses:
            li, ci = i0, i0 + 1
            z_in = x_res if (li == 0 and plan.win_in) else step_ins[li]
            delta, g_l, g_c, vecs = _pair_rdma_bwd(
                z_in, delta, li, ci, plan, tabs, d_in, d_out, base_cols)
            g_tabs[li], g_tabs[ci] = g_l, g_c
            if li == 0 and plan.fold_din:
                folded["din"] = vecs.pop(0)
            if ci == n_steps - 1 and plan.fold_dout:
                folded["dout"] = vecs.pop(0)
            if ci == n_steps - 1 and plan.fold_bias:
                folded["bias"] = vecs.pop(0)
            continue
        steps_here = seg[1:] if seg[0] == "pair" else (seg[1],)
        for off in range(len(steps_here) - 1, -1, -1):
            i = i0 + off
            step = steps_here[off]
            first, last = i == 0, i == n_steps - 1
            cf = tabs[i][0]
            d_blocks = _overlap_split(delta, plan.row_blocks)
            if step[0] == "cross":
                z_blocks = _overlap_split(step_ins[i], plan.row_blocks)
                outs = [_cross_bwd(
                    zb, db, cf, step[2], plan,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    has_bias=last and plan.fold_bias)
                    for zb, db in zip(z_blocks, d_blocks)]
                delta = jnp.concatenate([o[0] for o in outs], axis=0)
                g_tabs[i] = functools.reduce(jnp.add, [o[1] for o in outs])
                extras = _sum_vec_lists([o[2] for o in outs])
                if last and plan.fold_dout:
                    folded["dout"] = extras.pop(0)
                if last and plan.fold_bias:
                    folded["bias"] = extras.pop(0)
            else:
                z_in = x_res if (first and plan.win_in) else step_ins[i]
                z_blocks = _overlap_split(z_in, plan.row_blocks)
                outs = [_segment_bwd(
                    zb, db, cf, step[2], plan,
                    d_in=d_in if (first and plan.fold_din) else None,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    has_bias=last and plan.fold_bias,
                    col_base=base_cols if (first and plan.win_in) else None,
                    in_width=plan.in_width
                    if (first and plan.win_in) else None)
                    for zb, db in zip(z_blocks, d_blocks)]
                delta = jnp.concatenate([o[0] for o in outs], axis=0)
                g_tabs[i] = functools.reduce(jnp.add, [o[1] for o in outs])
                vecs = _sum_vec_lists([o[2] for o in outs])
                if first and plan.fold_din:
                    folded["din"] = vecs.pop(0)
                if last and plan.fold_dout:
                    folded["dout"] = vecs.pop(0)
                if last and plan.fold_bias:
                    folded["bias"] = vecs.pop(0)
    return delta, g_tabs, folded


# ---------------------------------------------------------------------------
# per-shard operator body
# ---------------------------------------------------------------------------

def _shard_fwd(plan: ShardPlan, tabs, d_in, d_out, bias, x2, collect: bool):
    fdt = x2.dtype
    ph = jnp.zeros((1,), fdt)
    base_cols = jax.lax.axis_index(AXIS) * plan.n_local
    if plan.in_width is None:
        z = x2                                 # the shard-resident slab
    elif plan.win_in:
        z = x2      # feature-complete: the first kernel run windows it
    else:
        z = _window_slab(x2, base_cols, plan.n_local, plan.in_width)
    x_res = x2 if plan.win_in else (z if plan.saves_x_res else ph)
    if plan.has_din and not plan.fold_din:
        z = z * d_in.astype(fdt)
    n_steps = len(plan.steps)
    if plan.overlap:
        z, step_ins = _overlap_steps_fwd(plan, tabs, d_in, d_out, bias, z,
                                         base_cols, collect)
    else:
        step_ins = []
        for i, (step, tab) in enumerate(zip(plan.steps, tabs)):
            first, last = i == 0, i == n_steps - 1
            if collect:
                step_ins.append(ph if (first and plan.win_in) else z)
            cf = tab[0]                  # drop the (1,) local shard axis
            if step[0] == "cross":
                z = _cross_fwd(
                    z, cf, step[2], plan,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    bias=bias if (last and plan.fold_bias) else None)
            else:
                z = _segment_fwd(
                    z, cf, step[2], plan,
                    d_in=d_in if (first and plan.fold_din) else None,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    bias=bias if (last and plan.fold_bias) else None,
                    col_base=base_cols
                    if (first and plan.win_in) else None,
                    in_width=plan.in_width
                    if (first and plan.win_in) else None)
    z_last = z
    if plan.has_dout and not plan.fold_dout:
        z = z * d_out.astype(fdt)
    if plan.has_bias and not plan.fold_bias:
        z = z + bias.astype(fdt)
    if collect:
        return z, (x_res, tuple(step_ins),
                   z_last if plan.saves_z_last else ph)
    return z


def _shard_bwd(plan: ShardPlan, tabs, d_in, d_out, bias, res, gy):
    x_res, step_ins, z_last = res
    fdt = gy.dtype
    ph = jnp.zeros((1,), _F32)
    base_cols = jax.lax.axis_index(AXIS) * plan.n_local
    # gy is always the (rows, n_local) slab cotangent: a rectangular
    # out_width arrives zero-padded to n by _sharded_core_bwd (see the
    # ShardPlan note on why the cotangent is not window-read), so the
    # padded lanes contribute exact zeros to every grad below with no
    # masking needed.
    gys = gy
    g_din = g_dout = g_bias = None
    if plan.has_bias and not plan.fold_bias:
        g_bias = jnp.sum(gys.astype(_F32), axis=0)
    if plan.has_dout and not plan.fold_dout:
        g_dout = jnp.sum(gys.astype(_F32) * z_last.astype(_F32), axis=0)
        delta = gys * d_out.astype(fdt)
    else:
        delta = gys
    n_steps = len(plan.steps)
    if plan.overlap:
        delta, g_list, folded = _overlap_steps_bwd(
            plan, tabs, d_in, d_out, res, delta, base_cols)
        # restore the (1,) local shard axis; reversed so the shared
        # epilogue's final [::-1] yields schedule order
        g_tabs = [g[None] for g in reversed(g_list)]
        g_din = folded.get("din", g_din)
        g_dout = folded.get("dout", g_dout)
        g_bias = folded.get("bias", g_bias)
    else:
        g_tabs = []
        for i in range(n_steps - 1, -1, -1):
            step = plan.steps[i]
            cf = tabs[i][0]
            first, last = i == 0, i == n_steps - 1
            if step[0] == "cross":
                delta, g, extras = _cross_bwd(
                    step_ins[i], delta, cf, step[2], plan,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    has_bias=last and plan.fold_bias)
                if last and plan.fold_dout:
                    g_dout = extras.pop(0)
                if last and plan.fold_bias:
                    g_bias = extras.pop(0)
            else:
                z_in = x_res if (first and plan.win_in) else step_ins[i]
                delta, g, vecs = _segment_bwd(
                    z_in, delta, cf, step[2], plan,
                    d_in=d_in if (first and plan.fold_din) else None,
                    d_out=d_out if (last and plan.fold_dout) else None,
                    has_bias=last and plan.fold_bias,
                    col_base=base_cols
                    if (first and plan.win_in) else None,
                    in_width=plan.in_width
                    if (first and plan.win_in) else None)
                if first and plan.fold_din:
                    g_din = vecs.pop(0)
                if last and plan.fold_dout:
                    g_dout = vecs.pop(0)
                if last and plan.fold_bias:
                    g_bias = vecs.pop(0)
            g_tabs.append(g[None])       # restore the (1,) local shard axis
    if plan.has_din and not plan.fold_din:
        g_din = jnp.sum(delta.astype(_F32) * x_res.astype(_F32), axis=0)
        delta = delta * d_in.astype(fdt)
    g_din = ph if g_din is None else g_din
    g_dout = ph if g_dout is None else g_dout
    g_bias = ph if g_bias is None else g_bias
    if plan.dp:
        # rows shard over the DP axes, so every batch-summed parameter grad
        # above is a per-DP-shard partial: reduce over dp (standard data-
        # parallel grad sync, parameter-sized — the feature axis itself is
        # never reduced).
        g_tabs = [jax.lax.psum(g, plan.dp) for g in g_tabs]
        if plan.has_din:
            g_din = jax.lax.psum(g_din, plan.dp)
        if plan.has_dout:
            g_dout = jax.lax.psum(g_dout, plan.dp)
        if plan.has_bias:
            g_bias = jax.lax.psum(g_bias, plan.dp)
    return delta, tuple(g_tabs[::-1]), g_din, g_dout, g_bias


# ---------------------------------------------------------------------------
# custom_vjp over the whole sharded operator
# ---------------------------------------------------------------------------

def _fwd_specs(plan: ShardPlan):
    in_specs = (plan.table_specs(), plan.vec_spec(plan.has_din),
                plan.vec_spec(plan.has_dout), plan.vec_spec(plan.has_bias),
                plan.x_spec())
    return in_specs, plan.act_spec(), plan.res_specs()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sharded_core(plan: ShardPlan, tables, d_in, d_out, bias, x2):
    """x2: (rows, in_width or n) row-major, rows pre-padded to block_rows
    when the kernel path is on.  Returns (rows, out_width or n)."""
    in_specs, y_spec, _ = _fwd_specs(plan)
    f = jax.shard_map(
        functools.partial(_shard_fwd, plan, collect=False),
        mesh=plan.mesh, in_specs=in_specs, out_specs=y_spec,
        check_vma=False)
    y2 = f(tables, d_in, d_out, bias, x2)
    if plan.out_width is not None:
        y2 = y2[:, :plan.out_width]
    return y2


def _sharded_core_fwd(plan, tables, d_in, d_out, bias, x2):
    in_specs, y_spec, res_specs = _fwd_specs(plan)
    f = jax.shard_map(
        functools.partial(_shard_fwd, plan, collect=True),
        mesh=plan.mesh, in_specs=in_specs, out_specs=(y_spec, res_specs),
        check_vma=False)
    y2, res = f(tables, d_in, d_out, bias, x2)
    if plan.out_width is not None:
        y2 = y2[:, :plan.out_width]
    return y2, (tables, d_in, d_out, bias, res)


def _sharded_core_bwd(plan, saved, gy2):
    tables, d_in, d_out, bias, res = saved
    in_specs, y_spec, res_specs = _fwd_specs(plan)
    if plan.out_width is not None:
        # Transport the cotangent as an even-width slab: the zero-pad is a
        # local op that fuses into the slab reshard, and the padded lanes
        # carry exact-zero cotangent (the transpose of the forward's
        # output slice).  Window-reading the (rows, out_width) cotangent
        # instead would force replicating it — a batch-proportional
        # all-gather whenever it flows back feature-sharded.
        # spmlint: allow[SPM002] — even-slab cotangent transport
        gy2 = jnp.pad(gy2, ((0, 0), (0, plan.n - plan.out_width)))
    out_specs = (y_spec, plan.table_specs(), plan.vec_spec(plan.has_din),
                 plan.vec_spec(plan.has_dout), plan.vec_spec(plan.has_bias))
    f = jax.shard_map(
        functools.partial(_shard_bwd, plan),
        mesh=plan.mesh,
        in_specs=in_specs[:4] + (res_specs, y_spec),
        out_specs=out_specs, check_vma=False)
    g_x2, g_tabs, g_din, g_dout, g_bias = f(tables, d_in, d_out, bias,
                                            res, gy2)
    if plan.in_width is not None:
        # the shard_map assembles the (rows, n) sharded delta; the primal
        # contract is (rows, in_width) — a local per-shard slice, and the
        # dropped lanes are the padded ones whose cotangent is discarded
        g_x2 = g_x2[:, :plan.in_width]

    def _vg(g, like, present):
        return g.astype(like.dtype) if present else jnp.zeros_like(like)

    g_tabs = tuple(g.astype(t.dtype) for g, t in zip(g_tabs, tables))
    return (g_tabs, _vg(g_din, d_in, plan.has_din),
            _vg(g_dout, d_out, plan.has_dout),
            _vg(g_bias, bias, plan.has_bias), g_x2)


_sharded_core.defvjp(_sharded_core_fwd, _sharded_core_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

# _resolve_kernel moved to core/eligibility.resolve_shard_kernel (the
# single fallback matrix), next to resolve_overlap / resolve_rdma.


def _rdma_cross_indices(steps, n_local: int) -> Tuple[int, ...]:
    """Cross-step indices executable as fused RDMA pair kernels: the pair's
    local run must plan to ONE kernel run (its stages' pair spans all fit
    one n_local-wide tile — true for every two_level cycle with
    n_local <= MAX_TILE).  The kernel pipelines at its own ``block_rows``
    granularity (one grid step per row block), independent of the coarser
    ``row_blocks`` the ppermute transport uses."""
    out = []
    i = 0
    for seg in overlap_segments(steps):
        if seg[0] == "pair":
            if len(plan_runs(n_local, seg[1][2])) == 1:
                out.append(i + 1)
            i += 2
        else:
            i += 1
    return tuple(out)


def spm_apply_sharded(params: dict, x: jax.Array, cfg, mesh: Mesh, *,
                      in_width: Optional[int] = None,
                      out_width: Optional[int] = None) -> jax.Array:
    """Feature-sharded SPM forward (+ closed-form grads via custom_vjp).

    Semantically identical to the unsharded ``spm_apply`` on the same
    params/config; the mesh's ``"model"`` axis size must equal
    ``cfg.n_shards``.  Rows co-shard over any pure-DP mesh axes
    ("pod"/"data") so batch-sharded activations enter without an
    all-gather.  Collectives issued: one collective-permute per cross-shard
    stage (two in the backward) — plus, only when DP axes exist, the
    standard parameter-sized grad psum over those axes in the backward.
    Under the overlap schedule (``cfg.overlap`` — see the module
    docstring) each of those permutes splits into one per row block with
    IDENTICAL total bytes, pipelined so a block's exchange hides under
    the other blocks' compute (in-kernel ``make_async_remote_copy`` on
    compiled TPU backends, per-block ppermute everywhere else).

    Rectangular widths: ``x`` stays ``(..., in_width)`` — it enters the
    shard_map feature-replicated and the FIRST shard-local kernel run reads
    this shard's n_local-wide window straight out of it (scalar-prefetch
    offset + in-VMEM iota mask against the global width), so no
    zero-padded square array is ever materialized in HBM; the backward
    remats through the same windowed read and the custom_vjp returns the
    input cotangent as ``(..., in_width)`` with exact-zero padded-lane
    parameter grads.  (Off the kernel path the window falls back to a
    local gather + mask in the shard body.)  The output leaves the
    shard_map as the assembled (rows, n) sharded array and is cut to
    ``out_width`` by one local per-shard slice, and the backward's
    cotangent enters as an even-width slab (local zero-pad fused into the
    reshard — see the ShardPlan note) — the two boundary XLA ops a
    rectangular operator still costs; under SPMD the edge shard's
    dead-tile compute is wall-clock-free (fully-live interior shards
    bound the step).
    """
    n = cfg.n
    if mesh.shape[AXIS] != cfg.n_shards:
        raise ValueError(
            f"mesh axis {AXIS!r} has size {mesh.shape[AXIS]}, operator has "
            f"n_shards={cfg.n_shards}")
    if in_width == n:
        in_width = None
    if out_width == n:
        out_width = None
    sched = cfg.pairing
    steps = plan_steps(n, sched.strides(), cfg.n_shards)
    n_local = n // cfg.n_shards

    in_w = in_width if in_width is not None else n
    lead = x.shape[:-1]
    rows = int(np.prod(lead, dtype=np.int64)) if lead else 1
    x2 = x.reshape(rows, in_w)

    from repro.parallel.sharding import data_axes
    dp = data_axes(mesh)
    dp_total = 1
    for a in dp:
        dp_total *= int(mesh.shape[a])

    backend_tpu = jax.default_backend() == "tpu"
    interpret = default_interpret()
    use_kernel = resolve_shard_kernel(cfg, steps, backend_tpu)
    overlap = resolve_overlap(cfg, steps, backend_tpu)
    rdma = overlap and resolve_rdma(use_kernel, backend_tpu, interpret)
    block_rows = 1
    if use_kernel:
        rows_per_dp = -(-rows // dp_total)
        block_rows = min(
            pick_block_rows_for_plan(plan_runs(n_local, step[2]),
                                     rows_per_dp,
                                     dtype_bytes=x.dtype.itemsize,
                                     overlap_bufs=rdma)
            for step in steps if step[0] == "local")
        if overlap:
            # the pipeline needs >= OVERLAP_ROW_BLOCKS kernel row blocks to
            # hide anything: trade block size down (never below the 8-row
            # VREG floor) until the slab yields that many — the per-block
            # VMEM working set only shrinks with it
            while (block_rows > 8
                   and rows_per_dp // block_rows < OVERLAP_ROW_BLOCKS):
                block_rows //= 2
    # rows must split evenly over the DP axes AND (kernel path) each
    # DP-local slab must be a block_rows multiple; padded rows are zeros,
    # contributing exact zeros to every batch-summed parameter grad.
    quantum = dp_total * block_rows
    padded = -(-rows // quantum) * quantum
    if padded != rows:
        # spmlint: allow[SPM002] row padding to the DP x row-block quantum
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))

    row_blocks = pick_row_blocks(padded // dp_total,
                                 block_rows) if overlap else ()
    rdma_crosses = (_rdma_cross_indices(steps, n_local)
                    if rdma else ())
    plan = ShardPlan(
        mesh=mesh, n=n, n_local=n_local, n_shards=cfg.n_shards,
        steps=steps, has_din=cfg.use_diag, has_dout=cfg.use_diag,
        has_bias=cfg.use_bias, use_kernel=use_kernel,
        block_rows=block_rows, interpret=interpret, dp=dp,
        in_width=in_width, out_width=out_width,
        row_blocks=row_blocks, rdma_crosses=rdma_crosses,
        quant_cf=use_kernel and bool(getattr(cfg, "quant_coeffs", False)))

    coeffs = spm_mod.stage_coeffs(params, cfg)
    tables = _step_tables(coeffs, steps, cfg.n_shards, n_local)
    # Pin the O(nL) tables replicated: without this, XLA back-propagates the
    # shard_map's P("model") spec into the gather above and then re-gathers
    # the result — a (tiny but) spurious all-gather in the forward HLO.  The
    # reshard at the shard_map boundary is then a local slice.  (The
    # BACKWARD still pays one parameter-sized all-gather assembling the
    # replicated coefficient grad from per-shard partials — inherent to
    # replicated params, and O(nL), never activation-sized.)
    rep = jax.sharding.NamedSharding(mesh, P())
    tables = tuple(jax.lax.with_sharding_constraint(t, rep) for t in tables)
    ph = jnp.zeros((1,), _F32)
    y2 = _sharded_core(
        plan, tables,
        params["d_in"] if cfg.use_diag else ph,
        params["d_out"] if cfg.use_diag else ph,
        params["bias"] if cfg.use_bias else ph,
        x2)
    if y2.shape[0] != rows:
        y2 = y2[:rows]
    out_w = out_width if out_width is not None else n
    return y2.reshape(lead + (out_w,))
