"""Pallas kernel allclose sweeps vs the pure-jnp oracle (kernels/ref.py).

Shape x dtype sweep per instructions; interpret mode on CPU.  Covers the
bare stage stack AND the full folded operator (diag + bias) forward and
backward, plus the knob plumbing through spm_apply / linear_apply."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SPMConfig, init_spm, kernel_eligible, spm_apply,
                        use_fused_kernel)
from repro.core.eligibility import quant_acts_eligible
from repro.core.linear import LinearConfig, init_linear, linear_apply
from repro.core.spm import stage_coeffs
from repro.kernels import quant as Q
from repro.kernels.ops import (plan_runs, plan_runs_for_rows, spm_stack_fused,
                               spm_stack_fused_q8, tile_cap_for_rows)
from repro.kernels.ref import (spm_full_ref, spm_runs_ref, spm_stack_grads_ref,
                               spm_stack_ref)
from repro.kernels.spm_stack import (pick_block_rows, spm_stack_bwd_kernel_call,
                                     spm_stack_kernel_call, vmem_bytes)

KEY = jax.random.PRNGKey(0)

SWEEP = [
    # (B, n, strides, dtype, block_rows, n_tile)
    (8, 128, (1, 2, 4, 8), jnp.float32, 8, 128),
    (16, 256, (1, 2, 4, 8, 16, 32, 64, 128), jnp.float32, 8, 256),
    (32, 512, (1, 4, 16, 64), jnp.float32, 16, 128),
    (8, 128, (1, 2, 4, 8), jnp.bfloat16, 8, 128),
    (16, 1024, (1, 2, 4, 8, 16), jnp.bfloat16, 8, 512),
    (8, 96, (1, 2, 4, 48), jnp.float32, 8, 96),    # non-power-of-two n
]


@pytest.mark.parametrize("B,n,strides,dtype,br,nt", SWEEP)
def test_fwd_kernel_matches_ref(B, n, strides, dtype, br, nt):
    x = jax.random.normal(KEY, (B, n)).astype(dtype)
    cf = (0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                  (len(strides), n // 2, 4)))
    y = spm_stack_kernel_call(x, cf, strides=strides, block_rows=br,
                              n_tile=nt, interpret=True)
    ref = spm_stack_ref(x.astype(jnp.float32), cf, strides).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("B,n,strides,dtype,br,nt", SWEEP[:4])
def test_bwd_kernel_matches_ref(B, n, strides, dtype, br, nt):
    x = jax.random.normal(KEY, (B, n)).astype(dtype)
    gy = jax.random.normal(jax.random.PRNGKey(2), (B, n)).astype(dtype)
    cf = (0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                  (len(strides), n // 2, 4)))
    gx, gcf = spm_stack_bwd_kernel_call(x, cf, gy, strides=strides,
                                        block_rows=br, n_tile=nt,
                                        interpret=True)
    rgx, rgcf = spm_stack_grads_ref(x.astype(jnp.float32), cf, strides,
                                    gy.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(rgx, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(gcf), np.asarray(rgcf),
                               atol=tol * 10, rtol=tol * 10)


def test_fused_wrapper_odd_batch_and_3d():
    n, strides = 256, (1, 2, 4, 8, 16, 32, 64, 128)
    x = jax.random.normal(KEY, (3, 7, n))       # odd rows, 3-D
    cf = 0.4 * jax.random.normal(KEY, (8, n // 2, 4))
    y = spm_stack_fused(x, cf, strides)
    np.testing.assert_allclose(y, spm_stack_ref(x, cf, strides), atol=1e-5)


def test_fused_wrapper_grads():
    n, strides = 128, (1, 2, 4, 8, 16, 32, 64)
    x = jax.random.normal(KEY, (5, n))
    cf = 0.4 * jax.random.normal(KEY, (7, n // 2, 4))
    f = lambda x, cf: jnp.sum(spm_stack_fused(x, cf, strides) ** 2)
    r = lambda x, cf: jnp.sum(spm_stack_ref(x, cf, strides) ** 2)
    g = jax.grad(f, argnums=(0, 1))(x, cf)
    gr = jax.grad(r, argnums=(0, 1))(x, cf)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_kernel_path_in_spm_apply():
    cfg0 = SPMConfig(n=64, n_stages=6, variant="general", use_kernel=False)
    cfg1 = SPMConfig(n=64, n_stages=6, variant="general", use_kernel=True)
    p = init_spm(KEY, cfg0)
    x = jax.random.normal(KEY, (5, 64))
    np.testing.assert_allclose(spm_apply(p, x, cfg0),
                               spm_apply(p, x, cfg1), atol=1e-5)


# ---------------------------------------------------------------------------
# full folded operator: y = D_out (B_L...B_1) D_in x + bias
# ---------------------------------------------------------------------------

def _full_operands(n, L, dkey=7):
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(1), (L, n // 2, 4))
    d_in = 1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(dkey), (n,))
    d_out = 1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(dkey + 1), (n,))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(dkey + 2), (n,))
    return cf, d_in, d_out, bias


FULL_SWEEP = [
    # (B, n, strides, dtype).  At a training row count the n=4096 stack
    # plans to TWO runs (stride 2048 has pair span 4096 > MAX_TILE): d_in
    # folds into run 0 and d_out/bias into run 1, exercising the boundary
    # split; in bf16 the activation crosses that boundary in bf16.
    (8, 128, (1, 2, 4, 8, 16, 64), jnp.float32),
    (5, 256, (1, 2, 4, 8, 16, 32, 64, 128), jnp.float32),
    (8, 128, (1, 2, 4, 8, 16, 64), jnp.bfloat16),
    (4, 4096, (1, 2, 4, 8, 1024, 2048), jnp.float32),
    (16, 4096, (1, 2, 4, 8, 1024, 2048), jnp.bfloat16),
]


def _full_sweep_ref(x, cf, strides, dtype, **kw):
    """The full-operator oracle as the fused path runs it at x's row
    count: f32 inside a run, the activation stored in ``dtype`` between
    runs (``spm_full_ref`` itself for a single run or f32)."""
    runs = plan_runs_for_rows(x.shape[-1], tuple(strides), x.shape[0],
                              jnp.dtype(dtype).itemsize)
    return spm_runs_ref(x.astype(jnp.float32), cf, [r for r, _ in runs],
                        dtype, **kw)


def test_full_sweep_has_multi_run_case():
    """Guard: a sweep case really runs a multi-run plan at its row count
    (so the boundary folding and the per-run backward routing stay
    covered), and the run-boundary oracle is ``spm_full_ref`` in f32."""
    multi = [(B, n, s, dt) for B, n, s, dt in FULL_SWEEP
             if len(plan_runs_for_rows(n, s, B, jnp.dtype(dt).itemsize)) > 1]
    assert multi
    _, n, strides, _ = multi[0]
    cf, d_in, d_out, bias = _full_operands(n, len(strides))
    x = jax.random.normal(KEY, (16, n))
    np.testing.assert_array_equal(
        _full_sweep_ref(x, cf, strides, jnp.float32, d_in=d_in,
                        d_out=d_out, bias=bias),
        spm_full_ref(x, cf, strides, d_in=d_in, d_out=d_out, bias=bias))


@pytest.mark.parametrize("B,n,strides,dtype", FULL_SWEEP)
def test_fused_full_operator_matches_ref(B, n, strides, dtype):
    cf, d_in, d_out, bias = _full_operands(n, len(strides))
    x = jax.random.normal(KEY, (B, n)).astype(dtype)
    y = spm_stack_fused(x, cf, strides, d_in=d_in, d_out=d_out, bias=bias)
    assert y.dtype == dtype
    ref = _full_sweep_ref(x, cf, strides, dtype, d_in=d_in, d_out=d_out,
                          bias=bias)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,n,strides,dtype", FULL_SWEEP)
def test_fused_full_operator_grads_match_autodiff(B, n, strides, dtype):
    """custom_vjp of the FULL fused operator == autodiff on the unfused
    reference, in every operand: x, coeffs, d_in, d_out, bias — incl. the
    bf16-activation backward (grads vs a bf16-quantized-forward oracle
    that also rounds the activation, and so the cotangent, at run
    boundaries; param grads stay f32 in-kernel)."""
    cf, d_in, d_out, bias = _full_operands(n, len(strides))
    x = jax.random.normal(KEY, (B, n)).astype(dtype)

    def f(x, cf, d_in, d_out, bias):
        y = spm_stack_fused(x, cf, strides, d_in=d_in, d_out=d_out,
                            bias=bias)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def r(x, cf, d_in, d_out, bias):
        y = _full_sweep_ref(x, cf, strides, dtype, d_in=d_in, d_out=d_out,
                            bias=bias)
        return jnp.sum(y ** 2)

    g = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, cf, d_in, d_out, bias)
    gr = jax.grad(r, argnums=(0, 1, 2, 3, 4))(x, cf, d_in, d_out, bias)
    # bf16: the fused path quantizes the activation I/O the f32 oracle
    # doesn't; grads agree to bf16 resolution
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("variant", ["general", "rotation"])
def test_spm_apply_full_fused_parity(variant):
    """spm_apply(use_kernel=True) == unfused path: outputs AND grads (the
    rotation variant exercises the theta -> coeffs chain outside the
    kernel)."""
    cfg0 = SPMConfig(n=64, n_stages=6, variant=variant, backward="custom",
                     use_kernel=False)
    cfg1 = SPMConfig(n=64, n_stages=6, variant=variant, backward="custom",
                     use_kernel=True)
    p = init_spm(KEY, cfg0)
    p["d_in"] = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(11), (64,))
    p["d_out"] = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(12), (64,))
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(13), (64,))
    x = jax.random.normal(KEY, (5, 64))
    np.testing.assert_allclose(spm_apply(p, x, cfg0), spm_apply(p, x, cfg1),
                               atol=1e-5)
    loss = lambda cfg: (lambda p, x: jnp.sum(spm_apply(p, x, cfg) ** 2))
    g0 = jax.grad(loss(cfg0), argnums=(0, 1))(p, x)
    g1 = jax.grad(loss(cfg1), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_spm_apply_fused_bf16_activations():
    """bf16 activation I/O with f32 in-VMEM compute (serve engine path)."""
    cfg0 = SPMConfig(n=128, n_stages=7, variant="general", use_kernel=False)
    cfg1 = SPMConfig(n=128, n_stages=7, variant="general", use_kernel=True)
    p = init_spm(KEY, cfg0)
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(14), (128,))
    x = jax.random.normal(KEY, (9, 128)).astype(jnp.bfloat16)
    y0 = spm_apply(p, x, cfg0)
    y1 = spm_apply(p, x, cfg1)
    assert y1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               atol=4e-2, rtol=4e-2)


# ---------------------------------------------------------------------------
# rectangular-native fused linears: the kernel reads (…, d_in), zero-fills
# to n in VMEM, and stores only the d_out output columns
# ---------------------------------------------------------------------------

RECT_CASES = [
    # (d_in, d_out, dtype)
    (48, 32, jnp.float32),     # d_in == n, narrow output only
    (48, 128, jnp.float32),    # d_in < d_out (FFN-up-like)
    (128, 48, jnp.float32),    # d_in > d_out (FFN-down-like)
    (47, 33, jnp.float32),     # odd dims (n = 48, both widths partial)
    (96, 256, jnp.bfloat16),   # bf16 I/O on the rectangular path
]


@pytest.mark.parametrize("d_in,d_out,dtype", RECT_CASES)
def test_linear_apply_fused_parity_rectangular(d_in, d_out, dtype):
    """Fused rectangular path == unfused XLA pad/compose/slice: outputs AND
    grads in every operand, with the input cotangent coming back
    (…, d_in).  bf16 compares at bf16 resolution with an absolute floor
    (the unfused path computes the stages in bf16; the kernel is f32 in
    VMEM)."""
    mk = lambda uk: LinearConfig(d_in=d_in, d_out=d_out, impl="spm_general",
                                 backward="custom", use_kernel=uk)
    lc0, lc1 = mk(False), mk(True)
    p = init_linear(KEY, lc0)
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(15), (lc0.n,))
    x = jax.random.normal(KEY, (6, d_in)).astype(dtype)
    y0, y1 = linear_apply(p, x, lc0), linear_apply(p, x, lc1)
    assert y1.shape == (6, d_out) and y1.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               atol=tol, rtol=tol)
    loss = lambda lc: (lambda p, x: jnp.sum(
        linear_apply(p, x, lc).astype(jnp.float32) ** 2))
    g0 = jax.grad(loss(lc0), argnums=(0, 1))(p, x)
    g1 = jax.grad(loss(lc1), argnums=(0, 1))(p, x)
    assert g1[1].shape == (6, d_in) and g1[1].dtype == dtype
    atol, rtol = (1e-4, 1e-4) if dtype == jnp.float32 else (0.25, 6e-2)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=atol, rtol=rtol)


def test_fused_rectangular_no_xla_pad_or_slice():
    """Acceptance: the fused rectangular linear_apply lowers with NO
    XLA-level jnp.pad and no feature-axis output slice — the zero-fill and
    the partial store live inside the kernel boundary runs.  (Uses the
    shared repro.analysis.jaxpr_walk walker, which visits every inner
    jaxpr except kernel bodies; the batch is a multiple of the row block
    so the only legitimate pad — row padding — is absent too.)"""
    from repro.analysis.jaxpr_walk import feature_axis_slices, primitive_names

    lc = LinearConfig(d_in=96, d_out=256, impl="spm_general",
                      backward="custom", use_kernel=True)
    p = init_linear(KEY, lc)
    x = jax.random.normal(KEY, (8, 96))
    jx = jax.make_jaxpr(lambda x: linear_apply(p, x, lc))(x)
    names = primitive_names(jx.jaxpr)
    assert "pad" not in names, f"XLA pad survived: {sorted(set(names))}"
    slices = feature_axis_slices(jx.jaxpr)
    assert slices == [], f"feature-axis output slice survived: {slices}"


def test_bwd_dead_tile_skip_zero_blocks():
    """ISSUE 4 acceptance: with ``out_width`` the backward grid visits only
    ceil(out_width / n_tile) feature tiles, the unvisited parameter-grad
    (and g_x) blocks come back EXACTLY zero (aliased zero-init, not
    computed), and the visited region matches the full-grid oracle.
    ``dead_from`` produces the same pruning for an interior run whose
    cotangent is already zero past the downstream run's skip point."""
    B, n, nt, strides = 8, 256, 64, (1, 2, 4)
    out_w = 100                         # vis = ceil(100/64) = 2 of 4 tiles
    x = jax.random.normal(KEY, (B, n))
    gy = jax.random.normal(jax.random.PRNGKey(2), (B, out_w))
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                 (len(strides), n // 2, 4))
    d_in = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n,))
    d_out = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (n,))
    out = spm_stack_bwd_kernel_call(x, cf, gy, d_in, d_out, strides=strides,
                                    block_rows=8, n_tile=nt, has_bias=True,
                                    out_width=out_w, interpret=True)
    gx, gcf, gdin, gdout, gbias = out
    # oracle: full-width gy with an explicit zero tail, full grid
    gy_full = jnp.pad(gy, ((0, 0), (0, n - out_w)))

    def ref(x, cf, d_in, d_out):
        z = spm_stack_ref(x * d_in, cf, strides)
        return jnp.sum(z * d_out * gy_full)

    rgx, rgcf, rgdin, rgdout = jax.grad(ref, argnums=(0, 1, 2, 3))(
        x, cf, d_in, d_out)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gcf), np.asarray(rgcf),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gdin), np.asarray(rgdin),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gdout), np.asarray(rgdout),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gbias),
                               np.asarray(jnp.sum(gy_full, axis=0)),
                               atol=1e-4, rtol=1e-4)
    # unvisited blocks (tiles 2..3: pair rows >= 64, columns >= 128) are
    # exact zeros — not small numbers: they were never computed
    assert np.all(np.asarray(gcf[:, 2 * (nt // 2):]) == 0)
    assert np.all(np.asarray(gx[:, 2 * nt:]) == 0)
    for v in (gdin, gdout, gbias):
        assert np.all(np.asarray(v[2 * nt:]) == 0)
    # dead_from: interior-run shape — full-width gy whose tail is already
    # exactly zero; the pruned grid must reproduce the full-grid grads
    gx2, gcf2 = spm_stack_bwd_kernel_call(x, cf, gy_full, strides=strides,
                                          block_rows=8, n_tile=nt,
                                          dead_from=out_w, interpret=True)
    rgx2, rgcf2 = spm_stack_grads_ref(x, cf, strides, gy_full)
    np.testing.assert_allclose(np.asarray(gx2), np.asarray(rgx2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gcf2), np.asarray(rgcf2),
                               atol=1e-3, rtol=1e-3)
    assert np.all(np.asarray(gcf2[:, 2 * (nt // 2):]) == 0)


@pytest.mark.parametrize("in_w,out_w", [
    (3000, 2500),   # both widths partial in their edge tiles
    (1500, 2500),   # in_w <= n - first-run n_tile: whole input feature
                    # tiles past the edge (the g_x width-vs-grid aliasing
                    # regime — the backward must widen g_x internally)
    (1500, 1800),   # both widths below the first/last run tile — here the
                    # plan's last run is a single 4096-wide tile, so the
                    # backward skip does NOT engage (dead-chain coverage
                    # lives in test_fused_dead_chain_non_monotone_tiles)
])
def test_fused_rectangular_multi_run_boundaries(in_w, out_w):
    """Rectangular widths on a MULTI-run plan (n=4096 splits in two):
    in_width masks only the first run, out_width only the last, the
    intermediate stays n-wide, and padded lanes get exactly-zero
    diag/bias grads."""
    n, strides = 4096, (1, 2, 4, 8, 1024, 2048)
    assert len(plan_runs(n, strides)) == 2
    cf, d_in, d_out, bias = _full_operands(n, len(strides))
    # 16 rows: above TINY_ROW_THRESHOLD, so the multi-run default plan
    # engages (tiny batches collapse to a single wide run by design)
    x = jax.random.normal(KEY, (16, in_w))

    def f(x, cf, d_in, d_out, bias):
        y = spm_stack_fused(x, cf, strides, d_in=d_in, d_out=d_out,
                            bias=bias, in_width=in_w, out_width=out_w)
        return jnp.sum(y ** 2)

    def r(x, cf, d_in, d_out, bias):
        xp = jnp.pad(x, ((0, 0), (0, n - in_w)))
        y = spm_full_ref(xp, cf, tuple(strides), d_in=d_in, d_out=d_out,
                         bias=bias)
        return jnp.sum(y[:, :out_w] ** 2)

    y = spm_stack_fused(x, cf, strides, d_in=d_in, d_out=d_out, bias=bias,
                        in_width=in_w, out_width=out_w)
    assert y.shape == (16, out_w)
    xp = jnp.pad(x, ((0, 0), (0, n - in_w)))
    ref = spm_full_ref(xp, cf, tuple(strides), d_in=d_in, d_out=d_out,
                       bias=bias)[:, :out_w]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    g = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, cf, d_in, d_out, bias)
    gr = jax.grad(r, argnums=(0, 1, 2, 3, 4))(x, cf, d_in, d_out, bias)
    assert g[0].shape == (16, in_w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)
    assert np.all(np.asarray(g[2][in_w:]) == 0)    # g_din past d_in
    assert np.all(np.asarray(g[3][out_w:]) == 0)   # g_dout past d_out
    assert np.all(np.asarray(g[4][out_w:]) == 0)   # g_bias past d_out


@pytest.mark.parametrize("in_w,out_w", [
    (None, 1800),   # square input, narrow output: every dead column holds
                    # real remat data, so a wrong skip corrupts grads
    (3000, 1200),   # narrowing with both widths partial
])
def test_fused_dead_chain_non_monotone_tiles(in_w, out_w):
    """Regression for the dead_from chain on a plan whose run tiles are
    NOT monotone (2048 -> 4096 -> 8): a larger-tile middle run spreads
    live cotangent across its whole edge tile, so the upstream run's dead
    boundary must be re-derived from EACH run's tile width — propagating
    the last run's boundary verbatim zeroed real gradients here."""
    n, strides = 4096, (1, 2, 4, 8, 1024, 2048, 1, 2)
    tiles = [t for _, t in plan_runs(n, strides)]
    assert len(tiles) == 3 and tiles[1] > tiles[0] > tiles[2], tiles
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                 (len(strides), n // 2, 4))
    xw = in_w if in_w is not None else n
    # 16 rows keep the non-monotone 3-run plan (tiny rows collapse it)
    x = jax.random.normal(KEY, (16, xw))

    def f(x, cf):
        y = spm_stack_fused(x, cf, strides, in_width=in_w, out_width=out_w)
        return jnp.sum(y ** 2)

    def r(x, cf):
        xp = jnp.pad(x, ((0, 0), (0, n - xw)))
        return jnp.sum(spm_stack_ref(xp, cf, strides)[:, :out_w] ** 2)

    g = jax.grad(f, argnums=(0, 1))(x, cf)
    gr = jax.grad(r, argnums=(0, 1))(x, cf)
    assert g[0].shape == (16, xw)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_windowed_col_base_kernel_mode():
    """The sharded windowed (col_base) kernel mode, driven directly as the
    distributed executor drives it per shard: the forward/backward read
    each shard's n_local-wide window straight out of the feature-complete
    operands, masking against GLOBAL widths in VMEM.  (The executor uses
    the x window; the symmetric gy window is exercised here to keep the
    kernel contract covered.)"""
    n, S, n_local, in_w, out_w = 64, 4, 16, 50, 40
    B, nt, strides = 8, 16, (1, 2, 4)
    x = jax.random.normal(KEY, (B, in_w))
    gy = jax.random.normal(jax.random.PRNGKey(2), (B, out_w))
    cf_l = 0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                   (len(strides), n_local // 2, 4))
    d_in = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n,))
    xp = jnp.pad(x, ((0, 0), (0, n - in_w)))
    gyp = jnp.pad(gy, ((0, 0), (0, n - out_w)))
    for j in range(S):
        base = jnp.asarray([j * (n_local // nt)], jnp.int32)
        d_loc = d_in[j * n_local:(j + 1) * n_local]
        slab = xp[:, j * n_local:(j + 1) * n_local]
        gy_slab = gyp[:, j * n_local:(j + 1) * n_local]
        y = spm_stack_kernel_call(x, cf_l, d_loc, None, None, base,
                                  strides=strides, block_rows=8, n_tile=nt,
                                  in_width=in_w, interpret=True)
        ref = spm_stack_ref(slab * d_loc, cf_l, strides)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        gx, gcf, gdin, gbias = spm_stack_bwd_kernel_call(
            x, cf_l, gy, d_loc, None, base, strides=strides, block_rows=8,
            n_tile=nt, has_bias=True, in_width=in_w, out_width=out_w,
            interpret=True)

        def f(slab, cf, d):
            return jnp.sum(spm_stack_ref(slab * d, cf, strides) * gy_slab)

        rgx, rgcf, rgd = jax.grad(f, argnums=(0, 1, 2))(slab, cf_l, d_loc)
        for a, b in ((gx, rgx), (gcf, rgcf), (gdin, rgd),
                     (gbias, jnp.sum(gy_slab, axis=0))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)


def test_use_kernel_fallback_rules():
    """Tri-state resolution: forced-on still falls back for odd n,
    permutation pairings, and custom_inverse; auto is off on CPU."""
    assert not use_fused_kernel(
        SPMConfig(n=9, n_stages=3, schedule="random", use_kernel=True))
    assert not use_fused_kernel(
        SPMConfig(n=16, n_stages=4, schedule="random", use_kernel=True))
    assert not use_fused_kernel(
        SPMConfig(n=16, n_stages=4, variant="rotation",
                  backward="custom_inverse", use_kernel=True))
    # sharded two_level WITHOUT a mesh context: just a stride schedule —
    # the fused kernel runs it unpartitioned.  (With a feature-sharding
    # mesh active, spm_apply routes to the distributed executor BEFORE
    # this check — parallel/spm_shard.py, tests/test_distributed.py.)
    assert use_fused_kernel(
        SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=4,
                  use_kernel=True))
    assert use_fused_kernel(
        SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=1,
                  use_kernel=True))
    assert kernel_eligible(SPMConfig(n=16, n_stages=4))
    auto = SPMConfig(n=16, n_stages=4)
    if jax.default_backend() != "tpu":
        assert not use_fused_kernel(auto)
    assert not use_fused_kernel(
        SPMConfig(n=16, n_stages=4, use_kernel=False))
    # odd-n fallback still computes correctly end to end
    cfg = SPMConfig(n=9, n_stages=3, schedule="random", use_kernel=True)
    p = init_spm(KEY, cfg)
    y = spm_apply(p, jax.random.normal(KEY, (4, 9)), cfg)
    assert y.shape == (4, 9) and bool(jnp.all(jnp.isfinite(y)))


def test_plan_runs_covers_schedule():
    runs = plan_runs(2048, (1, 2, 4, 8, 1024, 1, 2))
    flat = [s for r, _ in runs for s in r]
    assert flat == [1, 2, 4, 8, 1024, 1, 2]
    for strides, tile in runs:
        assert 2048 % tile == 0
        for s in strides:
            assert tile % (2 * s) == 0


def test_vmem_budget_respected():
    for nt in (128, 512, 2048):
        br = pick_block_rows(nt, 12)
        assert vmem_bytes(br, nt, 12) <= 12 * 2 ** 20 * 2  # within 2x budget
        assert br >= 8


# ---------------------------------------------------------------------------
# tiny-row (decode) plans
# ---------------------------------------------------------------------------

def test_plan_runs_for_rows_tiny_vs_training():
    """Decode-sized calls (rows <= TINY_ROW_THRESHOLD) re-plan under the
    widened VMEM tile cap — fewer, wider runs (fewer HBM round-trips per
    token) — while training-sized calls keep the default plan exactly."""
    from repro.core.eligibility import TINY_ROW_THRESHOLD, tiny_row_call
    from repro.kernels.ops import (MAX_TILE, plan_runs_for_rows,
                                   tile_cap_for_rows)
    from repro.kernels.spm_stack import pick_max_tile

    assert not tiny_row_call(0)
    assert all(tiny_row_call(r) for r in range(1, TINY_ROW_THRESHOLD + 1))
    assert not tiny_row_call(TINY_ROW_THRESHOLD + 1)

    n, strides = 4096, (1, 2, 4, 8, 1024, 2048)
    assert len(plan_runs(n, strides)) == 2        # default: 2 runs @ 2048
    assert tile_cap_for_rows(n, strides, 64) == MAX_TILE
    assert plan_runs_for_rows(n, strides, 64) == plan_runs(n, strides)

    assert pick_max_tile(n, len(strides)) >= n    # one 8-row block fits
    assert tile_cap_for_rows(n, strides, 4) >= n
    tiny = plan_runs_for_rows(n, strides, 4)
    assert len(tiny) == 1 and tiny[0][1] == n     # single full-width run
    # the runs cover the same stage sequence either way
    assert sum((list(r[0]) for r in tiny), []) == \
        sum((list(r[0]) for r in plan_runs(n, strides)), [])


def test_tiny_row_fused_matches_ref_and_grads():
    """A decode-shaped call (4 rows) through spm_stack_fused takes the
    single-run tiny plan and still matches the jnp oracle bitwise-close,
    forward and backward — the re-plan changes traffic, not math."""
    from repro.kernels.ops import plan_runs_for_rows

    n, strides = 4096, (1, 2, 2048)
    assert len(plan_runs_for_rows(n, strides, 4)) == 1   # tiny plan
    assert len(plan_runs(n, strides)) == 2               # training plan
    x = jax.random.normal(KEY, (4, n))
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(1),
                                 (len(strides), n // 2, 4))
    y = spm_stack_fused(x, cf, strides)
    ref = spm_stack_ref(x, cf, strides)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    g = jax.grad(lambda x, cf:
                 jnp.sum(spm_stack_fused(x, cf, strides) ** 2),
                 argnums=(0, 1))(x, cf)
    gr = jax.grad(lambda x, cf:
                  jnp.sum(spm_stack_ref(x, cf, strides) ** 2),
                  argnums=(0, 1))(x, cf)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)

# ---------------------------------------------------------------------------
# quantized fused path (test-pyramid layer 2): int8 activation I/O and
# per-stage int8 coefficient tables vs the f32 XLA reference.  Layer 1
# (quantizer primitives) is tests/test_quantization.py; layer 3 (sharded
# parity + compressed-pod convergence) is tests/test_distributed.py.
# ---------------------------------------------------------------------------


def _operator_gain(coeffs, d_in=None, d_out=None):
    """Row-sum-norm bound on the operator's amplification: every stage's
    2x2 mix amplifies an elementwise bound by at most
    max(|a|+|b|, |c|+|d|) over its pairs, the diagonals by their absmax.
    An upper bound on |y|_inf / |x|_inf, and on the gain from any
    internal point to the output."""
    a, b, c, d = (jnp.abs(coeffs[..., i]) for i in range(4))
    per_stage = jnp.max(jnp.maximum(a + b, c + d), axis=-1)   # (L,)
    g = jnp.prod(per_stage)
    for diag in (d_in, d_out):
        if diag is not None:
            g = g * jnp.max(jnp.abs(diag))
    return float(g)


def _quant_tol(x, coeffs, d_in=None, d_out=None):
    """Derived worst-case output bound for the quantized fused path — no
    magic constants, everything comes from the scale convention and the
    operands themselves.

    Each quantization event rounds to nearest on a grid with step
    absmax/127 at that point, so it injects at most absmax/254
    elementwise.  The magnitude anywhere in the chain is at most
    G * max|x| (G = ``_operator_gain``), and the downstream gain on any
    injected error is also at most G, so one event contributes at most
    G * (G * max|x|) / 254 ... except G bounds the WHOLE chain, so
    amplitude-at-event x gain-after-event is itself bounded by
    G * max|x|.  Events: activation I/O quantizes the input plus every
    run-boundary store (<= L + 1 of them, runs <= stages), coefficient
    quantization perturbs each of the L stages' two row entries.  Total:

        tol = 2 * (3 L + 2) * G * max|x| / 254

    with a final factor 2 of headroom for f32 accumulation ordering.
    Observed error sits ~20x below this bound while the bound stays well
    below the output scale, so a wrong-scale / wrong-tile bug trips it.
    """
    L = coeffs.shape[0]
    g = _operator_gain(coeffs, d_in, d_out)
    return 2.0 * (3 * L + 2) * g * float(jnp.max(jnp.abs(x))) / 254.0


QUANT_RECT = [
    # (d_in, d_out): FFN-up-like, FFN-down-like, odd dims, square
    (48, 128),
    (128, 48),
    (47, 33),
    (64, 64),
]


@pytest.mark.parametrize("d_in,d_out", QUANT_RECT)
@pytest.mark.parametrize("mode", ["acts", "coeffs", "both"])
def test_linear_apply_quantized_parity(d_in, d_out, mode):
    """Quantized fused linear vs the f32 XLA reference (use_kernel=False)
    across rectangular widths, within the tolerance DERIVED from the
    per-stage scale bound (``_quant_tol``) — not a magic constant.  Grads
    through the quantized path stay finite (straight-through for coeffs,
    dequantized cotangents for acts)."""
    qa, qc = mode in ("acts", "both"), mode in ("coeffs", "both")
    mk = lambda uk: LinearConfig(d_in=d_in, d_out=d_out, impl="spm_general",
                                 backward="custom", use_kernel=uk,
                                 quant_acts=uk and qa,
                                 quant_coeffs=uk and qc)
    lc_ref, lc_q = mk(False), mk(True)
    p = init_linear(KEY, lc_ref)
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(15), (lc_ref.n,))
    x = jax.random.normal(jax.random.PRNGKey(2), (8, d_in))
    y_ref = linear_apply(p, x, lc_ref)
    y_q = linear_apply(p, x, lc_q)
    assert y_q.shape == y_ref.shape and y_q.dtype == y_ref.dtype
    cf = stage_coeffs(p, lc_ref.spm_config())
    tol = _quant_tol(x, cf, p.get("d_in"), p.get("d_out"))
    err = float(jnp.max(jnp.abs(y_q - y_ref)))
    assert err <= tol, (err, tol)
    g = jax.grad(lambda p, x: jnp.sum(linear_apply(p, x, lc_q) ** 2),
                 argnums=(0, 1))(p, x)
    assert all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree.leaves(g))


def test_quant_coeffs_grads_match_predequantized_table():
    """quant_coeffs=True is numerically the f32 operator over the
    DEQUANTIZED table: outputs and grads (straight-through in coeffs)
    match running the plain fused kernel on ``dequantize_coeffs(
    quantize_coeffs(cf))`` to within a few ulp of f32 reassociation —
    single-stage is bitwise, multi-stage XLA:CPU FMA ordering costs ~1
    ulp per stage."""
    B, n, strides = 8, 128, (1, 2, 4, 8)
    x = jax.random.normal(KEY, (B, n))
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(3),
                                 (len(strides), n // 2, 4))
    dq = Q.dequantize_coeffs(*Q.quantize_coeffs(cf), jnp.float32)
    y_q = spm_stack_fused(x, cf, strides, quant_coeffs=True)
    y_d = spm_stack_fused(x, dq, strides)
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_d),
                               rtol=2e-6, atol=1e-6)
    g_q = jax.grad(lambda x, cf: jnp.sum(
        spm_stack_fused(x, cf, strides, quant_coeffs=True) ** 2),
        argnums=(0, 1))(x, cf)
    g_d = jax.grad(lambda x, cf: jnp.sum(
        spm_stack_fused(x, cf, strides) ** 2),
        argnums=(0, 1))(x, dq)
    for a, b in zip(g_q, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_quant_acts_ineligible_plan_falls_back_bitwise():
    """A non-uniform-tile training plan cannot chain int8 across runs:
    quant_acts must silently fall back to f32 I/O — BITWISE equal to the
    unquantized kernel path, not merely close."""
    B, n, strides = 64, 4096, (1, 2048)
    cap = tile_cap_for_rows(n, strides, B, dtype_bytes=4)
    runs = plan_runs(n, strides, cap)
    assert not quant_acts_eligible(runs), runs   # the premise of the test
    x = jax.random.normal(KEY, (B, n))
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(5),
                                 (len(strides), n // 2, 4))
    y_f32 = spm_stack_fused(x, cf, strides)
    y_q = spm_stack_fused(x, cf, strides, quant_acts=True)
    np.testing.assert_array_equal(np.asarray(y_f32), np.asarray(y_q))


def test_spm_stack_fused_q8_int8_end_to_end():
    """The inference entry: int8 rows in, int8 rows out, per-block scales
    riding alongside — dequantizing the result lands within the derived
    quantization bound of the f32 fused operator (which itself matches
    the XLA reference elsewhere in this file)."""
    B, n, strides = 16, 128, (1, 2, 4, 8, 16, 32, 64)
    br = 8
    x = jax.random.normal(KEY, (B, n))
    cf = 0.4 * jax.random.normal(jax.random.PRNGKey(7),
                                 (len(strides), n // 2, 4))
    di = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(8), (n,))
    do = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (n,))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(10), (n,))
    cap = tile_cap_for_rows(n, strides, B, dtype_bytes=1)
    (run,) = plan_runs(n, strides, cap)      # single uniform-tile run
    qx, xs = Q.quantize_blocks(x, br, run[1])
    qy, ys = spm_stack_fused_q8(qx, xs, cf, strides,
                                d_in=di, d_out=do, bias=bias)
    assert qy.dtype == jnp.int8 and qy.shape == (B, n)
    assert ys.shape == (B // br, n // run[1])
    y = Q.dequantize_blocks(qy, ys, br, run[1], jnp.float32)
    y_ref = spm_stack_fused(x, cf, strides, d_in=di, d_out=do, bias=bias)
    tol = _quant_tol(x, cf, di, do)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err <= tol, (err, tol)


def test_spm_stack_fused_q8_rejects_ineligible_plan():
    """Unlike the training entry (graceful f32 fallback), the int8-native
    entry has no f32 path to fall back to: a non-uniform-tile plan is a
    loud ValueError, not silent garbage."""
    B, n, strides = 64, 4096, (1, 2048)
    qx = jnp.zeros((B, n), jnp.int8)
    xs = jnp.ones((B // 8, 1), jnp.float32)
    cf = jnp.zeros((len(strides), n // 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="uniform-tile"):
        spm_stack_fused_q8(qx, xs, cf, strides)
