"""Stagewise Pairwise Mixers (SPM) — the paper's core operator.

Implements (paper §2):

    SPM(x) = D_out * (B_L ... B_1) * D_in * x + b

with each stage B_l made of n//2 independent 2x2 blocks on disjoint pairs.

Two parameterizations (paper §3):
  * variant="rotation":  one angle per pair, orthogonal by construction.
  * variant="general":   four scalars (a, b, c, d) per pair.

Both are normalized internally to a per-stage coefficient tensor
``coeffs[l] : (n_pairs, 4)`` holding (a, b, c, d); the rotation variant
derives (cos t, -sin t, sin t, cos t) from theta so the closed-form theta
gradient (paper eq. 9) emerges from chaining eq. 14 through the trig map.

Backward modes:
  * "autodiff"       — JAX reverse-mode through the factorized forward.
  * "custom"         — paper §4 closed-form VJP (custom_vjp, saves stage
                       inputs exactly as eqs. 12–14/15–19 require).
  * "custom_inverse" — rotation only: REVERSIBLE backward.  Stage inputs are
                       reconstructed from outputs via B_l^T = B_l^{-1}, so no
                       intermediate activations are stored (O(n) residuals
                       instead of O(nL)).  Beyond-paper memory optimization.

Fused kernel path: ``use_kernel`` (tri-state, see SPMConfig) routes the
WHOLE operator — diag, stages, and bias — through the Pallas kernel pair in
``kernels/ops.py`` with its own closed-form custom_vjp; the selectable
backward modes above only apply to the XLA composition fallback.

All apply functions act on the last axis of arbitrarily-batched inputs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import pairings
from repro.core.eligibility import kernel_eligible, use_fused_kernel
from repro.core.pairings import Schedule, Stage

__all__ = ["SPMConfig", "init_spm", "spm_apply", "spm_matrix", "stage_coeffs",
           "kernel_eligible", "use_fused_kernel"]


@dataclasses.dataclass(frozen=True)
class SPMConfig:
    """Static configuration of one SPM operator (hashable; safe to close over
    in jitted functions)."""

    n: int
    n_stages: int
    variant: str = "general"          # "general" | "rotation"
    schedule: str = "butterfly"       # pairings.make_schedule kinds
    use_diag: bool = True
    use_bias: bool = True
    backward: str = "autodiff"        # "autodiff" | "custom" | "custom_inverse"
    init_mode: str = "orthogonal"     # "orthogonal" | "identity"
    init_scale: float = 0.05
    n_shards: int = 1                 # for schedule="two_level"
    # Schedule granularity for "two_level", decoupled from the EXECUTION
    # shard count.  The stride sequence (the operator's math) is built for
    # ``schedule_shards`` blocks (default: ``n_shards``); ``n_shards`` only
    # says how many shards EXECUTE it.  A schedule built for S shards is
    # executable on any power-of-two divisor m of S (strides below n/m
    # become shard-local runs, the rest stay k*(n/m) partner exchanges), so
    # an elastic restart onto fewer chips keeps the SAME operator:
    # ``dataclasses.replace(cfg, n_shards=m, schedule_shards=S)`` restores
    # a checkpoint bit-for-bit onto the smaller mesh (train/checkpoint.py's
    # topology-independent restore; proven by the chaos parity harness).
    schedule_shards: Optional[int] = None
    seed: int = 0
    param_dtype: Any = jnp.float32
    # Fused full-operator Pallas kernel (kernels/ops.py): tri-state.
    #   None  — auto: ON whenever the schedule is eligible AND we are on a
    #           TPU backend (on CPU the kernel runs in interpret mode, which
    #           is only useful for validation, so auto stays on XLA).
    #   True  — force the fused path when eligible (interpret mode off-TPU;
    #           used by tests/benchmarks).
    #   False — never.
    # Eligibility (graceful fallback otherwise): all stages structured
    # (stride pairings), even n, and backward != "custom_inverse" (the
    # reversible backward stores outputs, incompatible with the in-VMEM
    # remat the kernel backward performs).
    use_kernel: Optional[bool] = None
    # Overlap-scheduled sharded executor (parallel/spm_shard.py): tri-state.
    #   None  — auto: row-block pipelined cross-shard exchanges on TPU
    #           backends only (where the ICI latency is real); off-TPU the
    #           step-serial full-slab schedule remains the fallback.
    #   True  — force the overlap SCHEDULE everywhere; off-TPU / interpret
    #           it runs with the per-block collective_permute transport
    #           (the parity-test proof path), on TPU pair segments use the
    #           in-kernel RDMA transport (make_async_remote_copy).
    #   False — keep the step-serial schedule.
    # Resolution lives in core/eligibility.resolve_overlap; only consulted
    # when the distributed executor engages (n_shards > 1 + mesh context).
    overlap: Optional[bool] = None
    # Int8 quantization knobs (kernels/quant.py scale conventions).  Both
    # change only BYTES MOVED — in-VMEM compute stays f32:
    #   quant_acts   — int8 activation I/O for the fused kernel runs
    #                  (per-(row-block, feature-tile) scales; requires a
    #                  uniform-tile run plan, falls back to f32 I/O
    #                  gracefully — core/eligibility.quant_acts_eligible).
    #                  Fused single-device path only; the XLA composition
    #                  and the distributed executor ignore it.
    #   quant_coeffs — int8 per-stage-scaled coefficient tables,
    #                  dequantized in VMEM; honored by the fused path AND
    #                  the distributed executor's shard-local runs.
    #                  Coefficient grads stay f32, computed from the same
    #                  dequantized values the forward used.
    quant_acts: bool = False
    quant_coeffs: bool = False
    # Int8 error-feedback compression of the cross-pod gradient all-reduce
    # (optim/compression.psum_compressed_ef).  Consumed by the TRAIN layer
    # (train/step.make_pod_train_step), not by the operator itself: the
    # knob rides here so one config object carries the whole quantization
    # posture of a run.
    compress_pod_grads: bool = False

    def __post_init__(self):
        if self.variant not in ("general", "rotation"):
            raise ValueError(f"bad variant {self.variant!r}")
        if self.backward == "custom_inverse" and self.variant != "rotation":
            raise ValueError("custom_inverse backward requires the rotation "
                             "variant (blocks must be orthogonal)")

    @functools.cached_property
    def pairing(self) -> Schedule:
        """The operator's pairing schedule (built once; the two_level kind
        uses ``schedule_shards`` — see that field — as its block split)."""
        return pairings.make_schedule(
            self.schedule, self.n, self.n_stages,
            n_shards=self.schedule_shards or self.n_shards, seed=self.seed)

    @property
    def n_pairs(self) -> int:
        """Pairs per stage (n // 2; the odd coordinate, if any, rides a
        residual 1x1 scale instead)."""
        return self.n // 2

    @property
    def odd(self) -> bool:
        """Odd operator width: each stage leaves one coordinate unpaired
        (scaled by ``res_scale``) and the fused kernel path is ineligible."""
        return self.n % 2 == 1

    def param_count(self) -> int:
        """Total learnable parameters of the operator: O(nL) stage
        coefficients (1 angle or 4 scalars per pair) plus the odd-n
        residual scales and the optional diagonals/bias — the paper's
        headline count vs the dense layer's n^2."""
        per_stage = self.n_pairs * (1 if self.variant == "rotation" else 4)
        total = self.n_stages * per_stage
        if self.odd:
            total += self.n_stages  # residual 1x1 scales
        if self.use_diag:
            total += 2 * self.n
        if self.use_bias:
            total += self.n
        return total


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def init_spm(key: jax.Array, cfg: SPMConfig) -> dict:
    """Near-identity / random-orthogonal init.  The paper does not prescribe
    an init; we default to random per-pair rotations (norm-preserving at
    init for BOTH variants) plus small noise, which keeps the composed
    operator well-conditioned at L=12 depth."""
    kt, km, kd = jax.random.split(key, 3)
    dt = cfg.param_dtype
    p: dict = {}
    if cfg.variant == "rotation":
        if cfg.init_mode == "identity":
            theta = cfg.init_scale * jax.random.normal(
                kt, (cfg.n_stages, cfg.n_pairs), dt)
        else:
            theta = jax.random.uniform(
                kt, (cfg.n_stages, cfg.n_pairs), dt,
                minval=-np.pi, maxval=np.pi)
        p["theta"] = theta
    else:
        if cfg.init_mode == "identity":
            eye = jnp.asarray([1.0, 0.0, 0.0, 1.0], dt)
            mix = (jnp.broadcast_to(eye, (cfg.n_stages, cfg.n_pairs, 4))
                   + cfg.init_scale * jax.random.normal(
                       km, (cfg.n_stages, cfg.n_pairs, 4), dt))
        else:
            th = jax.random.uniform(kt, (cfg.n_stages, cfg.n_pairs), dt,
                                    minval=-np.pi, maxval=np.pi)
            c, s = jnp.cos(th), jnp.sin(th)
            mix = (jnp.stack([c, -s, s, c], axis=-1)
                   + cfg.init_scale * jax.random.normal(
                       km, (cfg.n_stages, cfg.n_pairs, 4), dt))
        p["mix"] = mix
    if cfg.odd:
        p["res_scale"] = jnp.ones((cfg.n_stages,), dt)
    if cfg.use_diag:
        p["d_in"] = jnp.ones((cfg.n,), dt)
        p["d_out"] = jnp.ones((cfg.n,), dt)
    if cfg.use_bias:
        p["bias"] = jnp.zeros((cfg.n,), dt)
    return p


def stage_coeffs(params: dict, cfg: SPMConfig) -> jax.Array:
    """Normalize either parameterization to (L, n_pairs, 4) = (a, b, c, d)."""
    if cfg.variant == "rotation":
        th = params["theta"]
        c, s = jnp.cos(th), jnp.sin(th)
        return jnp.stack([c, -s, s, c], axis=-1)
    return params["mix"]


# ---------------------------------------------------------------------------
# single-stage application
# ---------------------------------------------------------------------------

def _mix_pairs(x0, x1, a, b, c, d):
    y0 = a * x0 + b * x1
    y1 = c * x0 + d * x1
    return y0, y1


def apply_stage(x: jax.Array, coeffs: jax.Array, stage: Stage,
                res_scale: Optional[jax.Array] = None,
                transpose: bool = False) -> jax.Array:
    """Apply one stage B_l (or B_l^T) to the last axis of x.

    coeffs: (n_pairs, 4).  transpose=True applies the transposed blocks
    [[a, c], [b, d]] on the same pairing — used by the closed-form backward
    (paper §4.2: g_{z-1} = B^T g_z).
    """
    a, b, c, d = (coeffs[:, 0], coeffs[:, 1], coeffs[:, 2], coeffs[:, 3])
    if transpose:
        b, c = c, b
    n = x.shape[-1]
    lead = x.shape[:-1]
    if stage.structured:
        s = stage.stride
        g = n // (2 * s)
        xr = x.reshape(lead + (g, 2, s))
        x0, x1 = xr[..., 0, :], xr[..., 1, :]
        ar, br, cr, dr = (v.reshape(g, s) for v in (a, b, c, d))
        y0, y1 = _mix_pairs(x0, x1, ar, br, cr, dr)
        return jnp.stack([y0, y1], axis=-2).reshape(lead + (n,))
    # general permutation pairing
    perm = stage.perm
    inv = np.argsort(perm)
    n_pairs = n // 2
    xg = x[..., perm]
    xp = xg[..., : 2 * n_pairs].reshape(lead + (n_pairs, 2))
    y0, y1 = _mix_pairs(xp[..., 0], xp[..., 1], a, b, c, d)
    yp = jnp.stack([y0, y1], axis=-1).reshape(lead + (2 * n_pairs,))
    if n % 2:
        rs = res_scale if res_scale is not None else jnp.ones((), x.dtype)
        resid = (xg[..., -1] * rs)[..., None]
        yp = jnp.concatenate([yp, resid], axis=-1)
    return yp[..., inv]


def apply_stage_inverse(y: jax.Array, coeffs: jax.Array, stage: Stage,
                        res_scale: Optional[jax.Array] = None) -> jax.Array:
    """Invert one stage.  For orthogonal (rotation) blocks this equals the
    transpose; implemented generally via the 2x2 inverse for robustness."""
    a, b, c, d = (coeffs[:, 0], coeffs[:, 1], coeffs[:, 2], coeffs[:, 3])
    det = a * d - b * c
    inv_coeffs = jnp.stack([d / det, -b / det, -c / det, a / det], axis=-1)
    inv_res = None if res_scale is None else 1.0 / res_scale
    return apply_stage(y, inv_coeffs, stage, res_scale=inv_res)


# ---------------------------------------------------------------------------
# core L-stage composition with selectable backward
# ---------------------------------------------------------------------------

def _forward_stages(coeffs: jax.Array, res_scales: Optional[jax.Array],
                    x: jax.Array, sched: Schedule,
                    collect: bool = False):
    """Run all stages; optionally return the list of stage inputs."""
    zs = []
    z = x
    for ell, stage in enumerate(sched.stages):
        if collect:
            zs.append(z)
        rs = None if res_scales is None else res_scales[ell]
        z = apply_stage(z, coeffs[ell], stage, res_scale=rs)
    return (z, zs) if collect else z


def _stage_grads(z_in: jax.Array, delta: jax.Array, coeffs: jax.Array,
                 stage: Stage, res_scale: Optional[jax.Array]):
    """Closed-form per-stage grads (paper eqs. 12–14 applied pairwise).

    Returns (g_input, g_coeffs, g_res_scale).  Batch dims of z_in/delta are
    summed into the parameter grads (paper §4 'Batch Setting').
    """
    n = z_in.shape[-1]
    lead = z_in.shape[:-1]
    bdims = tuple(range(len(lead)))

    if stage.structured:
        s = stage.stride
        g = n // (2 * s)
        zr = z_in.reshape(lead + (g, 2, s))
        dr = delta.reshape(lead + (g, 2, s))
        x0, x1 = zr[..., 0, :], zr[..., 1, :]
        d0, d1 = dr[..., 0, :], dr[..., 1, :]
        a, b, c, d = (coeffs[:, i].reshape(g, s) for i in range(4))
        # input grads: B^T delta  (eqs. 12–13)
        gx0 = a * d0 + c * d1
        gx1 = b * d0 + d * d1
        g_in = jnp.stack([gx0, gx1], axis=-2).reshape(lead + (n,))
        # parameter grads (eq. 14), summed over batch
        ga = jnp.sum(d0 * x0, axis=bdims).reshape(-1)
        gb = jnp.sum(d0 * x1, axis=bdims).reshape(-1)
        gc = jnp.sum(d1 * x0, axis=bdims).reshape(-1)
        gd = jnp.sum(d1 * x1, axis=bdims).reshape(-1)
        return g_in, jnp.stack([ga, gb, gc, gd], axis=-1), None

    perm = stage.perm
    inv = np.argsort(perm)
    n_pairs = n // 2
    zg = z_in[..., perm]
    dg = delta[..., perm]
    zp = zg[..., : 2 * n_pairs].reshape(lead + (n_pairs, 2))
    dp = dg[..., : 2 * n_pairs].reshape(lead + (n_pairs, 2))
    x0, x1 = zp[..., 0], zp[..., 1]
    d0, d1 = dp[..., 0], dp[..., 1]
    a, b, c, d = (coeffs[:, i] for i in range(4))
    gx0 = a * d0 + c * d1
    gx1 = b * d0 + d * d1
    gp = jnp.stack([gx0, gx1], axis=-1).reshape(lead + (2 * n_pairs,))
    g_rs = None
    if n % 2:
        rs = res_scale if res_scale is not None else jnp.ones((), z_in.dtype)
        g_res_lane = dg[..., -1] * rs
        g_rs = jnp.sum(dg[..., -1] * zg[..., -1])
        gp = jnp.concatenate([gp, g_res_lane[..., None]], axis=-1)
    g_in = gp[..., inv]
    ga = jnp.sum(d0 * x0, axis=bdims)
    gb = jnp.sum(d0 * x1, axis=bdims)
    gc = jnp.sum(d1 * x0, axis=bdims)
    gd = jnp.sum(d1 * x1, axis=bdims)
    return g_in, jnp.stack([ga, gb, gc, gd], axis=-1), g_rs


def _make_core(sched: Schedule, mode: str):
    """Build the L-stage composition with the requested backward mode.

    Signature: core(coeffs (L, n_pairs, 4), res_scales (L,)|None, x) -> y.
    res_scales is passed as an array always (ones when unused) to keep the
    custom_vjp signature uniform.
    """

    if mode == "autodiff":
        def core(coeffs, res_scales, x):
            return _forward_stages(coeffs, res_scales, x, sched)
        return core

    if mode == "custom":
        @jax.custom_vjp
        def core(coeffs, res_scales, x):
            return _forward_stages(coeffs, res_scales, x, sched)

        def fwd(coeffs, res_scales, x):
            y, zs = _forward_stages(coeffs, res_scales, x, sched,
                                    collect=True)
            return y, (coeffs, res_scales, tuple(zs))

        def bwd(res, gy):
            coeffs, res_scales, zs = res
            g_coeffs = []
            g_rs = []
            delta = gy
            for ell in range(len(sched.stages) - 1, -1, -1):
                stage = sched.stages[ell]
                rs = res_scales[ell]
                delta, gc, grs = _stage_grads(zs[ell], delta, coeffs[ell],
                                              stage, rs)
                g_coeffs.append(gc)
                g_rs.append(grs if grs is not None
                            else jnp.zeros((), delta.dtype))
            g_coeffs = jnp.stack(g_coeffs[::-1], axis=0)
            g_rs = jnp.stack(g_rs[::-1], axis=0)
            return g_coeffs, g_rs, delta

        core.defvjp(fwd, bwd)
        return core

    if mode == "custom_inverse":
        @jax.custom_vjp
        def core(coeffs, res_scales, x):
            return _forward_stages(coeffs, res_scales, x, sched)

        def fwd(coeffs, res_scales, x):
            y = _forward_stages(coeffs, res_scales, x, sched)
            return y, (coeffs, res_scales, y)  # O(n) residuals: outputs only

        def bwd(res, gy):
            coeffs, res_scales, y = res
            g_coeffs = []
            g_rs = []
            delta = gy
            z = y
            for ell in range(len(sched.stages) - 1, -1, -1):
                stage = sched.stages[ell]
                rs = res_scales[ell]
                # reconstruct the stage INPUT from its output (reversibility)
                z = apply_stage_inverse(z, coeffs[ell], stage, res_scale=rs)
                delta, gc, grs = _stage_grads(z, delta, coeffs[ell], stage, rs)
                g_coeffs.append(gc)
                g_rs.append(grs if grs is not None
                            else jnp.zeros((), delta.dtype))
            g_coeffs = jnp.stack(g_coeffs[::-1], axis=0)
            g_rs = jnp.stack(g_rs[::-1], axis=0)
            return g_coeffs, g_rs, delta

        core.defvjp(fwd, bwd)
        return core

    raise ValueError(f"unknown backward mode {mode!r}")


@functools.lru_cache(maxsize=None)
def _cached_core(sched: Schedule, mode: str):
    return _make_core(sched, mode)


# ---------------------------------------------------------------------------
# public apply
# ---------------------------------------------------------------------------

# kernel_eligible / use_fused_kernel moved to core/eligibility.py (the
# single fallback matrix shared with the distributed executor); re-exported
# here unchanged for back-compat.


@obs.scoped("spm")
def spm_apply(params: dict, x: jax.Array, cfg: SPMConfig, *,
              in_width: Optional[int] = None,
              out_width: Optional[int] = None) -> jax.Array:
    """Full SPM forward: y = D_out * (B_L ... B_1) * D_in * x + bias.

    ``in_width`` / ``out_width`` embed a rectangular map (d_in -> d_out,
    each <= n) in the square operator: x is (..., in_width), treated as
    zero-padded to n, and only the first ``out_width`` output columns are
    returned.  On the fused kernel path the padding/slicing happens inside
    the kernel boundary runs (no XLA pad/slice, no dead output columns);
    the distributed executor window-reads the boundary operands per shard
    (docs/sharding.md); the XLA composition fallback realizes the same
    semantics with an explicit pad + slice around the square operator.
    """
    n = cfg.n
    if in_width == n:
        in_width = None
    if out_width == n:
        out_width = None
    expect = in_width if in_width is not None else n
    if x.shape[-1] != expect:
        raise ValueError(f"expected (..., {expect}), got {x.shape}")
    sched = cfg.pairing
    if cfg.n_shards > 1:
        # Distributed two_level path: with a feature-sharding mesh context
        # active (parallel/ctx.activation_sharding(shard_feature=True))
        # whose "model" axis matches n_shards, shard-local runs execute on
        # the shard-resident slab and cross-shard stages lower to
        # collective_permute partner exchanges (parallel/spm_shard.py).
        from repro.parallel import ctx as par_ctx        # lazy: keeps core
        from repro.parallel import spm_shard             # import-light
        mesh = par_ctx.feature_mesh(cfg.n_shards)
        if mesh is not None and spm_shard.sharded_eligible(cfg, sched):
            return spm_shard.spm_apply_sharded(
                params, x, cfg, mesh, in_width=in_width, out_width=out_width)
    if use_fused_kernel(cfg, sched):
        # Fused full-operator path: the diag multiplies and bias add are
        # folded into the boundary runs of the kernel plan (zero extra HBM
        # round-trips), and the custom_vjp covers the whole operator.
        # Coefficients stay in their param dtype (f32): the kernel computes
        # f32 in VMEM regardless of the activation I/O dtype, and the
        # rotation variant's theta -> (a, b, c, d) chain differentiates
        # outside the kernel through the coefficient cotangent.
        from repro.kernels import ops as kernel_ops  # lazy: keeps core light
        return kernel_ops.spm_stack_fused(
            x, stage_coeffs(params, cfg), sched.strides(),
            d_in=params["d_in"] if cfg.use_diag else None,
            d_out=params["d_out"] if cfg.use_diag else None,
            bias=params["bias"] if cfg.use_bias else None,
            in_width=in_width, out_width=out_width,
            quant_acts=cfg.quant_acts, quant_coeffs=cfg.quant_coeffs)
    if in_width is not None:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, n - in_width)]
        x = jnp.pad(x, pad)  # spmlint: allow[SPM002] XLA fallback path
    coeffs = stage_coeffs(params, cfg).astype(x.dtype)
    res_scales = params.get("res_scale")
    if res_scales is None:
        res_scales = jnp.ones((cfg.n_stages,), x.dtype)
    else:
        res_scales = res_scales.astype(x.dtype)
    z = x
    if cfg.use_diag:
        z = z * params["d_in"].astype(x.dtype)
    core = _cached_core(sched, cfg.backward)
    z = core(coeffs, res_scales, z)
    if cfg.use_diag:
        z = z * params["d_out"].astype(x.dtype)
    if cfg.use_bias:
        z = z + params["bias"].astype(x.dtype)
    if out_width is not None:
        z = z[..., :out_width]
    return z


def spm_matrix(params: dict, cfg: SPMConfig) -> jax.Array:
    """Materialize the full n x n operator (tests/analysis only, O(n^2 L)).

    Returns W such that spm_apply(params, x) == W @ x + bias.
    """
    eye = jnp.eye(cfg.n, dtype=jnp.float32)
    p = dict(params)
    bias = p.pop("bias", None)
    cols = spm_apply({**p, "bias": jnp.zeros((cfg.n,))} if cfg.use_bias else p,
                     eye, cfg)
    return cols.T  # rows of output per basis vector -> transpose
