"""Multi-device parity harness for the distributed two_level SPM executor.

conftest.py forbids setting ``--xla_force_host_platform_device_count``
globally (smoke tests and benches must see exactly 1 device), so the
multi-device tests run OUT OF PROCESS: the single parent-side test re-execs
pytest on this very file in a subprocess whose ``XLA_FLAGS`` force 8 host
devices (and whose env marks it as the worker); the worker-side tests —
guarded by that env var — then collect and the parent asserts the child
suite passed, forwarding its output on failure.

Worker coverage (ISSUE 3 + ISSUE 4 acceptance):
  * sharded ``spm_apply`` == unsharded reference, forward AND grads
    (params + input), f32 and bf16, on 2/4/8-way meshes;
  * even and odd-factor n, rectangular in/out widths, use_diag/use_bias
    on and off, both SPM variants, the fused-kernel path inside shard_map
    (interpret mode), and a multi-axis ("data", "model") mesh;
  * the kernel-native boundaries: diag/bias folded into the boundary
    kernel runs (cases whose schedule ends on a local step fold BOTH
    sides) and rectangular widths served by windowed (col_base) kernel
    reads, including jaxpr acceptance (no pad, no unfused diag/bias
    elementwise ops in the shard body, a single local output slice) and
    HLO acceptance for the rectangular case;
  * HLO acceptance: the lowered sharded module contains collective-permute
    and NO all-gather / all-reduce of the feature axis (the backward's one
    all-gather is the O(nL) replicated coefficient-grad assembly, bounded
    by parameter bytes).

The schedule-planning tests at the top are device-free and run in both the
parent and the worker.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

WORKER_ENV = "SPM_DISTRIBUTED_WORKER"
N_DEV = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _in_worker() -> bool:
    return os.environ.get(WORKER_ENV) == "1"


# ---------------------------------------------------------------------------
# device-free planning units (both processes)
# ---------------------------------------------------------------------------

def test_plan_steps_groups_local_runs_and_tags_crosses():
    from repro.core.pairings import two_level_schedule
    from repro.parallel.spm_shard import plan_steps

    strides = two_level_schedule(64, 8, 4).strides()   # n_local = 16
    steps = plan_steps(64, strides, 4)
    kinds = [s[0] for s in steps]
    assert kinds == ["local", "cross", "cross", "local"], steps
    assert steps[0][2] == (1, 2, 4, 8)        # one fused run of locals
    assert steps[1][2] == 1 and steps[2][2] == 2   # k of s=16, s=32
    # stage bookkeeping: local offset + run length meets the next cross
    assert steps[0][1] == 0 and steps[1][1] == 4 and steps[2][1] == 5
    with pytest.raises(ValueError):
        plan_steps(64, (3,), 4)               # 64 % 6 != 0: invalid stage
    with pytest.raises(ValueError):
        plan_steps(48, (8,), 8)               # straddles n_local=6 blocks


def test_sharded_eligible_rules():
    from repro.core.spm import SPMConfig
    from repro.parallel.spm_shard import sharded_eligible

    ok = SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=4)
    assert sharded_eligible(ok)
    assert not sharded_eligible(
        SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=1))
    assert not sharded_eligible(          # odd n_local=3: stride-1 fallback
        SPMConfig(n=24, n_stages=4, schedule="two_level", n_shards=8))
    assert not sharded_eligible(          # reversible backward stores outputs
        SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=4,
                  variant="rotation", backward="custom_inverse"))
    assert not sharded_eligible(          # permutation pairings
        SPMConfig(n=64, n_stages=4, schedule="random", n_shards=4))


def test_rdma_pair_plan_and_placeholder_residuals():
    """Device-free structure of the TPU RDMA dispatch: a {local -> cross}
    pair whose local run plans to one kernel run is marked as an RDMA
    cross, and its saved stage input becomes a replicated placeholder
    spec (the backward kernel remats it in VMEM) — the rest of the
    residual layout is untouched."""
    from jax.sharding import Mesh, PartitionSpec as P

    import jax
    from repro.core.pairings import two_level_schedule
    from repro.parallel.spm_shard import (ShardPlan, _rdma_cross_indices,
                                          plan_steps)

    steps = plan_steps(64, two_level_schedule(64, 8, 4).strides(), 4)
    assert [s[0] for s in steps] == ["local", "cross", "cross", "local"]
    # the paired cross (idx 1) is RDMA-able; the unpaired one (idx 2) not
    assert _rdma_cross_indices(steps, 16) == (1,)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    plan = ShardPlan(mesh=mesh, n=64, n_local=16, n_shards=4, steps=steps,
                     has_din=True, has_dout=True, has_bias=True,
                     use_kernel=True, block_rows=8, interpret=False,
                     row_blocks=(8, 8), rdma_crosses=(1,))
    assert plan.overlap
    assert [s[0] for s in plan.segments] == ["pair", "one", "one"]
    _, step_ins, _ = plan.res_specs()
    assert step_ins[1] == P(None)            # RDMA cross: placeholder
    assert step_ins[0] != P(None) and step_ins[2] != P(None)
    serial = ShardPlan(mesh=mesh, n=64, n_local=16, n_shards=4,
                       steps=steps, has_din=True, has_dout=True,
                       has_bias=True, use_kernel=True, block_rows=8,
                       interpret=False)
    assert not serial.overlap
    assert serial.res_specs()[1][1] != P(None)


# ---------------------------------------------------------------------------
# parent: re-exec this file under forced device count
# ---------------------------------------------------------------------------

if not _in_worker():

    def test_distributed_suite_in_subprocess():
        env = dict(os.environ)
        env[WORKER_ENV] = "1"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count="
                              f"{N_DEV}")
        env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=1500, cwd=REPO, env=env)
        assert r.returncode == 0, (
            f"multi-device worker suite failed (rc={r.returncode}):\n"
            f"--- stdout ---\n{r.stdout[-6000:]}\n"
            f"--- stderr ---\n{r.stderr[-2000:]}")
        assert "passed" in r.stdout


# ---------------------------------------------------------------------------
# worker: the actual multi-device tests
# ---------------------------------------------------------------------------

else:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.analysis.hlo_match import (assert_bwd_gather_bounded,
                                          assert_permute_only)
    from repro.core.spm import SPMConfig, init_spm, spm_apply
    from repro.launch.hlo_analysis import collective_bytes
    from repro.parallel import spm_shard
    from repro.parallel.ctx import activation_sharding, feature_mesh

    KEY = jax.random.PRNGKey(0)

    def _mesh(shards: int) -> Mesh:
        return Mesh(np.asarray(jax.devices()[:shards]).reshape(shards),
                    ("model",))

    def test_worker_sees_forced_devices():
        assert jax.device_count() == N_DEV

    CASES = [
        # (id, n, shards, L, dtype, diag, bias, kernel, variant, in_w, out_w)
        ("pow2_2way", 64, 2, 6, "f32", True, True, False, "general",
         None, None),
        ("pow2_4way", 64, 4, 8, "f32", True, True, False, "general",
         None, None),
        ("pow2_8way", 64, 8, 7, "f32", True, True, False, "general",
         None, None),
        ("oddfactor_n96", 96, 4, 8, "f32", True, True, False, "general",
         None, None),
        ("oddfactor_local48", 48, 4, 6, "f32", True, True, False, "general",
         None, None),
        ("no_diag_no_bias", 64, 4, 8, "f32", False, False, False, "general",
         None, None),
        ("rect_narrowing", 64, 4, 8, "f32", True, True, False, "general",
         50, 40),
        ("rect_widening", 64, 4, 8, "f32", True, True, False, "general",
         40, 60),
        ("rotation_variant", 64, 4, 6, "f32", True, True, False, "rotation",
         None, None),
        ("fused_kernel_runs", 64, 4, 6, "f32", True, True, True, "general",
         None, None),
        # L=7 on n=64/4 shards ends the cycle on a local step, so BOTH
        # boundaries fold into kernel runs (d_in into the first, d_out/bias
        # into the last) and rectangular widths use the windowed
        # (col_base) kernel reads on both sides.
        ("fused_fold_both", 64, 4, 7, "f32", True, True, True, "general",
         None, None),
        ("fused_rect", 64, 4, 7, "f32", True, True, True, "general",
         50, 40),
        ("fused_rect_widen", 64, 4, 7, "f32", True, True, True, "general",
         40, 60),
        ("fused_rect_bf16", 64, 4, 7, "bf16", True, True, True, "general",
         50, 40),
        ("fused_no_diag_bias", 64, 4, 7, "f32", False, False, True,
         "general", None, None),
        ("fused_8way_rect", 64, 8, 9, "f32", True, True, True, "general",
         50, 40),
        ("fused_rotation_fold", 64, 4, 7, "f32", True, True, True,
         "rotation", None, None),
        ("bf16", 64, 4, 8, "bf16", True, True, False, "general",
         None, None),
        ("bf16_rect", 64, 4, 6, "bf16", True, True, False, "general",
         50, 40),
    ]

    @pytest.mark.parametrize(
        "case", CASES, ids=[c[0] for c in CASES])
    def test_sharded_matches_unsharded_fwd_and_grads(case):
        (_, n, shards, L, dt, diag, bias, kernel, variant,
         in_w, out_w) = case
        dtype = jnp.bfloat16 if dt == "bf16" else jnp.float32
        f_tol = dict(atol=5e-2, rtol=5e-2) if dt == "bf16" else \
            dict(atol=2e-5, rtol=2e-5)
        g_tol = dict(atol=2e-1, rtol=2e-1) if dt == "bf16" else \
            dict(atol=2e-4, rtol=2e-4)

        def cfg_for(use_kernel):
            return SPMConfig(
                n=n, n_stages=L, variant=variant, schedule="two_level",
                n_shards=shards, use_diag=diag, use_bias=bias,
                backward="custom", use_kernel=use_kernel)

        cfg = cfg_for(kernel)
        ref_cfg = cfg_for(False)
        p = init_spm(KEY, cfg)
        d_in = in_w if in_w is not None else n
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, d_in))
        x = x.astype(dtype)
        kw = dict(in_width=in_w, out_width=out_w)

        def ref_loss(p, x):
            y = spm_apply(p, x, ref_cfg, **kw)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        y_ref = jax.jit(lambda p, x: spm_apply(p, x, ref_cfg, **kw))(p, x)
        g_ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(p, x)

        mesh = _mesh(shards)
        with activation_sharding(mesh, shard_feature=True):
            assert feature_mesh(shards) is mesh      # ctx is live
            assert spm_shard.sharded_eligible(cfg)   # and the case routes

            def sh_loss(p, x):
                y = spm_apply(p, x, cfg, **kw)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            y = jax.jit(lambda p, x: spm_apply(p, x, cfg, **kw))(p, x)
            g = jax.jit(jax.grad(sh_loss, argnums=(0, 1)))(p, x)

        out_d = out_w if out_w is not None else n
        assert y.shape == (2, 3, out_d) and y.dtype == dtype
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), **f_tol)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                **g_tol),
            g[0], g_ref[0])
        np.testing.assert_allclose(np.asarray(g[1], np.float32),
                                   np.asarray(g_ref[1], np.float32), **g_tol)

    def test_parity_on_multi_axis_mesh_with_batch_sharded_input():
        """The production meshes carry ("data", "model") with activations
        batch-sharded over "data": rows must co-shard into the executor
        (NO batch all-gather) and parameter grads must psum over the DP
        axes only — fwd and grads still match the unsharded reference."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=4,
                        backward="custom", use_kernel=False)
        p = init_spm(KEY, cfg)
        x = jax.random.normal(KEY, (8, 64))

        def loss(p, x):
            return jnp.sum(spm_apply(p, x, cfg) ** 2)

        y_ref = spm_apply(p, x, cfg)
        g_ref = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        with activation_sharding(mesh, shard_feature=True):
            fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg))
            y = fwd(p, xs)
            # batch enters sharded: permute-only, no all-gather/all-reduce
            assert_permute_only(fwd.lower(p, xs).compile().as_text())
            bwd = jax.jit(jax.grad(loss, argnums=(0, 1)))
            g = bwd(p, xs)
            # backward communicates parameter-sized grads only: the table
            # assembly all-gather + the DP psum — never activations
            param_bytes = (cfg.n_stages * (cfg.n // 2) * 4 + 3 * cfg.n) * 4
            assert_permute_only(bwd.lower(p, xs).compile().as_text(),
                                require_permute=False,
                                allow={"all-gather": 2 * param_bytes,
                                       "all-reduce": 2 * param_bytes})
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4),
            g[0], g_ref[0])
        np.testing.assert_allclose(np.asarray(g[1]), np.asarray(g_ref[1]),
                                   atol=2e-4, rtol=2e-4)

    def test_no_route_without_context_or_on_mismatched_mesh():
        """Outside a feature-sharding block (or with the wrong model-axis
        size) the operator must keep its unsharded semantics."""
        cfg = SPMConfig(n=64, n_stages=6, schedule="two_level", n_shards=4,
                        backward="custom", use_kernel=False)
        p = init_spm(KEY, cfg)
        x = jax.random.normal(KEY, (4, 64))
        y_ref = spm_apply(p, x, cfg)            # no context at all
        assert feature_mesh(4) is None
        with activation_sharding(_mesh(8), shard_feature=True):
            assert feature_mesh(4) is None       # 8-way mesh, 4-shard op
            y = spm_apply(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=0, rtol=0)

    def test_hlo_collective_permute_only_on_feature_axis():
        """ISSUE 3 acceptance: the compiled sharded path communicates via
        collective-permute; the feature axis is never all-gathered or
        all-reduced.  Backward may all-gather the O(nL) coefficient-grad
        tables (replicated-param assembly) — bounded by parameter bytes,
        strictly below the smallest activation buffer."""
        cfg = SPMConfig(n=64, n_stages=8, schedule="two_level", n_shards=8,
                        backward="custom", use_kernel=False)
        p = init_spm(KEY, cfg)
        rows = 128
        x = jax.random.normal(KEY, (rows, 64))
        mesh = _mesh(8)
        with activation_sharding(mesh, shard_feature=True):
            fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg))
            assert_permute_only(fwd.lower(p, x).compile().as_text())

            bwd = jax.jit(jax.grad(
                lambda p, x: jnp.sum(spm_apply(p, x, cfg) ** 2),
                argnums=(0, 1)))
            param_bytes = cfg.n_stages * (cfg.n // 2) * 4 * 4
            act_bytes = rows * cfg.n * 4
            assert 2 * param_bytes < act_bytes     # the bound is meaningful
            # permute-only with the one bounded all-gather budget also
            # asserts the permute actually exists in the backward module
            assert_permute_only(bwd.lower(p, x).compile().as_text(),
                                allow={"all-gather": 2 * param_bytes})

    # -- overlap-scheduled executor (ISSUE 5) -------------------------------

    OVERLAP_CASES = [
        # (id, n, shards, L, dtype, diag, bias, kernel, in_w, out_w)
        ("ov_2way", 64, 2, 6, "f32", True, True, False, None, None),
        ("ov_4way", 64, 4, 8, "f32", True, True, False, None, None),
        ("ov_8way", 64, 8, 9, "f32", True, True, False, None, None),
        ("ov_kernel", 64, 4, 7, "f32", True, True, True, None, None),
        ("ov_kernel_8way", 64, 8, 9, "f32", True, True, True, None, None),
        ("ov_no_diag_bias", 64, 4, 8, "f32", False, False, True,
         None, None),
        ("ov_rect", 64, 4, 7, "f32", True, True, True, 50, 40),
        ("ov_rect_widen", 64, 4, 7, "f32", True, True, True, 40, 60),
        ("ov_bf16", 64, 4, 8, "bf16", True, True, False, None, None),
        ("ov_bf16_kernel_rect", 64, 4, 7, "bf16", True, True, True,
         50, 40),
    ]

    @pytest.mark.parametrize(
        "case", OVERLAP_CASES, ids=[c[0] for c in OVERLAP_CASES])
    def test_overlap_matches_serial_and_unsharded(case):
        """ISSUE 5 acceptance: the overlap-scheduled executor (row-block
        pipelined cross-shard exchanges; per-block ppermute transport in
        interpret mode — the same schedule code the TPU RDMA path runs)
        matches BOTH the step-serial sharded executor and the unsharded
        reference, forward and grads, with the row-block pipeline actually
        engaged (> 1 block)."""
        from repro.core.eligibility import resolve_overlap
        _, n, shards, L, dt, diag, bias, kernel, in_w, out_w = case
        dtype = jnp.bfloat16 if dt == "bf16" else jnp.float32
        f_tol = dict(atol=5e-2, rtol=5e-2) if dt == "bf16" else \
            dict(atol=2e-5, rtol=2e-5)
        g_tol = dict(atol=2e-1, rtol=2e-1) if dt == "bf16" else \
            dict(atol=2e-4, rtol=2e-4)

        def cfg_for(overlap, use_kernel=kernel):
            return SPMConfig(
                n=n, n_stages=L, schedule="two_level", n_shards=shards,
                use_diag=diag, use_bias=bias, backward="custom",
                use_kernel=use_kernel, overlap=overlap)

        cfg_ov, cfg_ser = cfg_for(True), cfg_for(False)
        ref_cfg = cfg_for(False, use_kernel=False)
        steps = spm_shard.plan_steps(n, cfg_ov.pairing.strides(), shards)
        assert resolve_overlap(cfg_ov, steps, False)       # forced on CPU
        assert not resolve_overlap(cfg_ser, steps, False)
        p = init_spm(KEY, cfg_ov)
        d_in = in_w if in_w is not None else n
        # rows sized so the kernel path yields > 1 row block per shard
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 40, d_in))
        x = x.astype(dtype)
        kw = dict(in_width=in_w, out_width=out_w)

        def loss(cfg):
            return lambda p, x: jnp.sum(
                spm_apply(p, x, cfg, **kw).astype(jnp.float32) ** 2)

        y_ref = jax.jit(lambda p, x: spm_apply(p, x, ref_cfg, **kw))(p, x)
        g_ref = jax.jit(jax.grad(loss(ref_cfg), argnums=(0, 1)))(p, x)
        mesh = _mesh(shards)
        with activation_sharding(mesh, shard_feature=True):
            y_ov = jax.jit(
                lambda p, x: spm_apply(p, x, cfg_ov, **kw))(p, x)
            y_ser = jax.jit(
                lambda p, x: spm_apply(p, x, cfg_ser, **kw))(p, x)
            g_ov = jax.jit(jax.grad(loss(cfg_ov), argnums=(0, 1)))(p, x)
            g_ser = jax.jit(jax.grad(loss(cfg_ser), argnums=(0, 1)))(p, x)

        out_d = out_w if out_w is not None else n
        assert y_ov.shape == (4, 40, out_d) and y_ov.dtype == dtype
        # overlap vs serial is the sharp claim: identical math, re-blocked
        # rows — in f32 the parameter grads agree to reordering noise.  In
        # bf16 the XLA fallback batch-sums in bf16, so re-blocking changes
        # the accumulation grouping itself (the overlap grouping is the
        # more accurate one: shorter bf16 chains combined in f32) and the
        # comparison needs the same cancellation-aware tolerance as the
        # reference
        ser_g_tol = (dict(atol=1e-3, rtol=1e-3) if dt == "f32"
                     else dict(atol=1.0, rtol=2e-1))
        np.testing.assert_allclose(np.asarray(y_ov, np.float32),
                                   np.asarray(y_ser, np.float32), **f_tol)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                **ser_g_tol),
            g_ov, g_ser)
        # vs the unsharded reference the bf16 tolerance must absorb
        # near-cancellation residue: the XLA reference accumulates in bf16
        # over 160 rows (per-term epsilon ~0.008 of grads ~O(10^2)), so
        # near-zero elements keep an O(1) absolute residue the kernel's
        # f32 accumulation does not reproduce
        if dt == "bf16":
            g_tol["atol"] = 1.0
        np.testing.assert_allclose(np.asarray(y_ov, np.float32),
                                   np.asarray(y_ref, np.float32), **f_tol)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                **g_tol),
            g_ov, g_ref)

    def test_overlap_pipeline_actually_blocks_the_rows():
        """The engaged plan must pipeline > 1 row block (the schedule
        degenerates to step-serial at 1), and the per-block exchanges must
        leave the HLO collective-permute-only with the TOTAL permute bytes
        unchanged — re-blocking splits each stage's exchange, it never
        duplicates or re-routes bytes."""
        from repro.launch.hlo_analysis import (V5E, peaks,
                                               sharded_stage_traffic)
        from repro.parallel.spm_shard import pick_row_blocks
        cfg = SPMConfig(n=64, n_stages=8, schedule="two_level", n_shards=8,
                        backward="custom", use_kernel=False, overlap=True,
                        use_diag=False, use_bias=False)
        p = init_spm(KEY, cfg)
        rows = 16
        x = jax.random.normal(KEY, (rows, 64))
        assert len(pick_row_blocks(rows, 1)) > 1
        steps = spm_shard.plan_steps(64, cfg.pairing.strides(), 8)
        model = sharded_stage_traffic(64 // 8, rows, steps, dtype_bytes=4,
                                      overlap=True, hw=peaks(V5E))
        with activation_sharding(_mesh(8), shard_feature=True):
            fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg))
            hlo = fwd.lower(p, x).compile().as_text()
        assert_permute_only(hlo)
        cb = collective_bytes(hlo)
        assert cb["collective-permute"] == model["permute_bytes_per_chip"]
        # the model's books balance and the overlap split is non-trivial
        assert (model["exposed_permute_bytes_per_chip"]
                + model["hidden_permute_bytes_per_chip"]
                == model["permute_bytes_per_chip"])
        assert model["hidden_permute_bytes_per_chip"] > 0

    def test_permute_traffic_matches_model():
        """The HLO's collective-permute bytes equal the modeled per-stage
        slab exchanges (hlo_analysis.sharded_stage_traffic)."""
        from repro.launch.hlo_analysis import (V5E, peaks,
                                               sharded_stage_traffic)
        cfg = SPMConfig(n=64, n_stages=8, schedule="two_level", n_shards=8,
                        backward="custom", use_kernel=False,
                        use_diag=False, use_bias=False)
        p = init_spm(KEY, cfg)
        rows = 16
        x = jax.random.normal(KEY, (rows, 64))
        steps = spm_shard.plan_steps(64, cfg.pairing.strides(), 8)
        model = sharded_stage_traffic(64 // 8, rows, steps, dtype_bytes=4,
                                      hw=peaks(V5E))
        with activation_sharding(_mesh(8), shard_feature=True):
            fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg))
            cb = collective_bytes(fwd.lower(p, x).compile().as_text())
        assert cb["collective-permute"] == model["permute_bytes_per_chip"]

    # -- kernel-native boundary acceptance (ISSUE 4) ------------------------

    # eqn traversal lives in the shared analysis library now; the old
    # inline ``_walk_eqns`` helper became jaxpr_walk.split_shard_map.
    from repro.analysis.jaxpr_walk import (activation_pads,
                                           feature_axis_slices,
                                           split_shard_map)

    def test_shard_body_has_no_unfused_diag_bias_or_window_ops():
        """ISSUE 4 acceptance (fold + windowed reads): on an all-local
        schedule with diag + bias and rectangular widths, the shard body
        is kernel-native — no elementwise diag/bias mul/add on the slab,
        no pad/slice/gather of activations: every boundary op lives inside
        the Pallas kernel runs."""
        cfg = SPMConfig(n=64, n_stages=4, schedule="two_level", n_shards=4,
                        backward="custom", use_kernel=True)
        p = init_spm(KEY, cfg)
        rows = 8                       # multiple of block_rows: no row pad
        x = jax.random.normal(KEY, (rows, 50))
        with activation_sharding(_mesh(4), shard_feature=True):
            steps = spm_shard.plan_steps(64, cfg.pairing.strides(), 4)
            assert all(s[0] == "local" for s in steps)
            jx = jax.make_jaxpr(lambda p, x: spm_apply(
                p, x, cfg, in_width=50, out_width=40))(p, x)
        inside, outside = split_shard_map(jx.jaxpr)
        slab_rows = rows               # no DP axes: full rows per shard
        for e in inside:
            out_shapes = [v.aval.shape for v in e.outvars]
            slabby = any(len(s) == 2 and s[0] == slab_rows
                         for s in out_shapes)
            assert not (slabby and e.primitive.name in
                        ("mul", "add", "sub", "select_n", "pad", "gather",
                         "dynamic_slice")), \
                f"unfused slab op in shard body: {e.primitive.name}"
            if e.primitive.name == "slice":
                assert not any(len(s) == 2 and s[0] == slab_rows
                               for s in out_shapes), "slab slice in body"

    def test_cross_ending_schedule_folds_boundary_into_mix_epilogue():
        """PR 5 leftover closed: a schedule ENDING on cross stages folds
        d_out/bias onto the final mix epilogue's store instead of a
        separate post-walk pass.  The fold is scale-ON-STORE (d_out
        multiplies the mixed result AFTER the add) so it stays bitwise the
        unfolded op — elastic re-sharding classifies the same pinned
        stage local on a wider mesh and the two paths must agree.  Pinned
        structurally: the shard body's slab-shaped ops are EXACTLY the
        two-sided mix per cross stage (four muls, two adds, one role
        select — the order-preserving form _cross_mix documents) plus the
        ONE store-scale d_out mul and the single bias ride-along add on
        the last; no second d_out broadcast and no other elementwise op
        touches the slab."""
        from collections import Counter
        for use_bias in (True, False):
            cfg = SPMConfig(n=64, n_stages=6, schedule="two_level",
                            n_shards=4, backward="custom", use_kernel=True,
                            use_bias=use_bias)
            p = init_spm(KEY, cfg)
            rows = 8
            x = jax.random.normal(KEY, (rows, 64))
            steps = spm_shard.plan_steps(64, cfg.pairing.strides(), 4)
            assert steps[-1][0] == "cross"   # the premise of the test
            n_cross = sum(1 for s in steps if s[0] == "cross")
            with activation_sharding(_mesh(4), shard_feature=True):
                jx = jax.make_jaxpr(lambda p, x: spm_apply(p, x, cfg))(p, x)
            inside, _ = split_shard_map(jx.jaxpr)
            slab = Counter()
            for e in inside:
                if any(len(v.aval.shape) == 2 and v.aval.shape[0] == rows
                       for v in e.outvars):
                    slab[e.primitive.name] += 1
            assert slab["mul"] == 4 * n_cross + 1, dict(slab)
            assert slab["add"] == 2 * n_cross + int(use_bias), dict(slab)
            assert slab["select_n"] == n_cross, dict(slab)
            for prim in ("sub", "pad", "gather", "dynamic_slice"):
                assert slab[prim] == 0, dict(slab)

    def test_sharded_rect_no_pad_single_output_slice():
        """ISSUE 4 acceptance (rectangular widths): the sharded
        rectangular forward contains NO pad primitive and no
        activation-shaped gather; the only feature-axis slice is the final
        (rows, n) -> (rows, out_width) output extraction (one local
        per-shard op — shard_map outputs must be evenly sharded).  The
        backward's only activation-shaped pad is the even-slab cotangent
        transport (rows, out_width) -> (rows, n) — the slice's exact
        transpose, local and fused into the slab reshard (its other pads
        assemble the O(nL) coefficient tables)."""
        n, in_w, out_w, rows = 64, 50, 40, 8
        cfg = SPMConfig(n=n, n_stages=7, schedule="two_level", n_shards=4,
                        backward="custom", use_kernel=True)
        p = init_spm(KEY, cfg)
        x = jax.random.normal(KEY, (rows, in_w))
        kw = dict(in_width=in_w, out_width=out_w)
        with activation_sharding(_mesh(4), shard_feature=True):
            jxf = jax.make_jaxpr(lambda p, x: spm_apply(p, x, cfg, **kw))(
                p, x)
            jxb = jax.make_jaxpr(jax.grad(
                lambda p, x: jnp.sum(spm_apply(p, x, cfg, **kw) ** 2),
                argnums=(0, 1)))(p, x)
        inside, outside = split_shard_map(jxf.jaxpr)
        all_fwd = inside + outside
        assert not any(e.primitive.name == "pad" for e in all_fwd), \
            "XLA pad survived in the sharded rectangular forward"
        for e in all_fwd:
            if e.primitive.name == "gather":
                assert not (len(e.outvars[0].aval.shape) == 2
                            and e.outvars[0].aval.shape[0] == rows), \
                    "activation gather on the kernel path"
        feat_slices = feature_axis_slices(jxf.jaxpr, rows=rows)
        assert feat_slices == [((rows, n), (rows, out_w))], feat_slices
        act_pads = activation_pads(jxb.jaxpr, rows=rows)
        assert act_pads == [((rows, out_w), (rows, n))], act_pads

    def test_sharded_rect_hlo_collectives_bounded():
        """ISSUE 4 acceptance (HLO): the compiled rectangular sharded path
        communicates via collective-permute; no all-gather/all-reduce in
        the forward, and the backward's all-gather stays bounded by the
        O(nL) replicated-parameter grad assembly PLUS the one inherent
        jit-boundary replication of the indivisible-width g_x output.
        rows is chosen large enough that every activation buffer exceeds
        the parameter bound (same meaningfulness guard as the square HLO
        test), so a batch-scaled cotangent gather cannot hide under it —
        excluding exactly the regression a replicated windowed-gy read
        would introduce (the even-slab cotangent transport avoids it)."""
        n, in_w, out_w, rows = 64, 50, 40, 64
        cfg = SPMConfig(n=n, n_stages=7, schedule="two_level", n_shards=4,
                        backward="custom", use_kernel=True)
        p = init_spm(KEY, cfg)
        x = jax.random.normal(KEY, (rows, in_w))
        kw = dict(in_width=in_w, out_width=out_w)
        with activation_sharding(_mesh(4), shard_feature=True):
            fwd = jax.jit(lambda p, x: spm_apply(p, x, cfg, **kw))
            hlo_f = fwd.lower(p, x).compile().as_text()
            bwd = jax.jit(jax.grad(
                lambda p, x: jnp.sum(spm_apply(p, x, cfg, **kw) ** 2),
                argnums=(0, 1)))
            hlo_b = bwd.lower(p, x).compile().as_text()
        assert_permute_only(hlo_f)
        param_bytes = (cfg.n_stages * (cfg.n // 2) * 4 + 3 * cfg.n) * 4
        act_bytes = rows * out_w * 4   # the smallest activation buffer
        assert 2 * param_bytes < act_bytes   # the bound is meaningful
        # The one allowed activation-sized backward gather: replicating
        # the (rows, in_width) input cotangent at the jit boundary — a
        # width-50 array has no expressible even "model" sharding, so ANY
        # transport design pays it when g_x leaves the jit (shard width
        # rounds 50 up to 4*ceil(50/4) lanes).  The bound stays strictly
        # below what a windowed-gy replication would add on top
        # (+ rows*out_w*4), which is the regression this test excludes.
        gx_gather = rows * (-(-in_w // 4) * 4) * 4
        assert_bwd_gather_bounded(hlo_b, param_bytes=param_bytes,
                                  extra_gather_bytes=gx_gather)

    def test_psum_compressed_under_shard_map():
        """The int8 gradient all-reduce under a REAL shard_map pod axis
        (8 forced host devices): every member quantizes against the
        axis-max scale (pmax), the int8 payloads psum in int32, and each
        member dequantizes to the identical replicated result — matching
        the explicit host-side int8-sum reference."""
        from jax.sharding import PartitionSpec as P

        from repro.optim.compression import _amax_scale, psum_compressed

        mesh = Mesh(np.asarray(jax.devices()).reshape(N_DEV), ("pod",))
        # wildly different per-member magnitudes: local-scale quantization
        # would disagree on the dequant grid across members
        g = jnp.stack([(2.0 if i % 2 else 0.01) *
                       jax.random.normal(jax.random.fold_in(KEY, i), (64,))
                       for i in range(N_DEV)])
        f = jax.jit(jax.shard_map(
            lambda gi: psum_compressed({"w": gi[0]}, "pod")["w"][None],
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod")))
        out = np.asarray(f(g))
        s_max = float(max(_amax_scale(g[i]) for i in range(N_DEV)))
        q = np.clip(np.round(np.asarray(g, np.float64) / s_max), -127, 127)
        ref = q.sum(axis=0) * s_max
        for i in range(N_DEV):
            np.testing.assert_allclose(out[i], ref, rtol=1e-5, atol=1e-6)

    # -----------------------------------------------------------------
    # quantized parity (test-pyramid layer 3): int8 coefficient tables
    # under the sharded executor, serial and overlap, vs the f32
    # unsharded reference — tolerance derived from the per-stage scale
    # bound, not a magic constant
    # -----------------------------------------------------------------

    def _coeff_quant_bound_l2(x, p, cfg):
        """Worst-case L2 output perturbation from per-stage int8
        coefficient quantization — derived, and TIGHT enough to stay well
        below the signal (the elementwise row-sum bound is not: near-
        rotation stages cost ~sqrt(2) each there vs ~1 spectrally).

        A stage is block-diagonal 2x2s, so its spectral norm is the max
        pair singular value sigma_l (computed exactly); its quantization
        perturbs each entry by <= amax_l/254, a block-diagonal Delta with
        spectral norm <= 2*amax_l/254 = amax_l/127.  Routing stage l's
        perturbation through prefix amplitude and suffix gain:

            ||Delta y||_2 <= sum_l (G2 / sigma_l) * (amax_l/127) * ||x||_2

        with G2 = max|d_in| * max|d_out| * prod_l sigma_l, plus a factor
        2 of f32-accumulation headroom."""
        from repro.core.spm import stage_coeffs
        cf = stage_coeffs(p, cfg)
        a, b, c, d = cf[..., 0], cf[..., 1], cf[..., 2], cf[..., 3]
        e = a * a + b * b + c * c + d * d
        det = a * d - b * c
        sig = jnp.sqrt(
            (e + jnp.sqrt(jnp.maximum(e * e - 4 * det * det, 0.0))) / 2)
        sig_l = jnp.max(sig, axis=-1)                     # (L,)
        amax_l = jnp.max(jnp.abs(cf), axis=(1, 2))        # quant grids
        g2 = jnp.prod(sig_l)
        for diag in ("d_in", "d_out"):
            if diag in p:
                g2 = g2 * jnp.max(jnp.abs(p[diag]))
        per_stage = (g2 / sig_l) * (amax_l / 127.0)
        return 2.0 * float(jnp.sum(per_stage)) * \
            float(jnp.linalg.norm(x.astype(jnp.float32)))

    QUANT_SHARD_CASES = [
        # (shards, overlap)
        (2, False), (4, False), (8, False), (4, True), (8, True),
    ]

    @pytest.mark.parametrize(
        "shards,overlap", QUANT_SHARD_CASES,
        ids=[f"{s}way_{'overlap' if o else 'serial'}"
             for s, o in QUANT_SHARD_CASES])
    def test_sharded_quant_coeffs_parity(shards, overlap):
        """quant_coeffs=True through the sharded kernel executor (serial
        and row-block-overlapped) vs the unsharded f32 XLA reference,
        within the derived per-stage scale bound.  Note the sharded path
        quantizes each shard's LOCAL coefficient slab per stage (its own
        amax) while the fused single-device path uses the whole table's
        per-stage amax — so quantized paths are each compared against the
        f32 reference, never bitwise against each other.  Overlap vs
        serial WITHIN the sharded path is the sharp claim: identical
        tables, identical quantization grouping, re-blocked rows only —
        the forward must agree exactly."""
        L = 7
        cfg_q = SPMConfig(n=64, n_stages=L, schedule="two_level",
                          n_shards=shards, backward="custom",
                          use_kernel=True, overlap=overlap,
                          quant_coeffs=True)
        cfg_ser_q = SPMConfig(n=64, n_stages=L, schedule="two_level",
                              n_shards=shards, backward="custom",
                              use_kernel=True, overlap=False,
                              quant_coeffs=True)
        ref_cfg = SPMConfig(n=64, n_stages=L, schedule="two_level",
                            n_shards=shards, backward="custom",
                            use_kernel=False)
        p = init_spm(KEY, cfg_q)
        # rows sized so the overlap cases pipeline > 1 row block
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 40, 64))

        def loss(cfg):
            return lambda p, x: jnp.sum(spm_apply(p, x, cfg) ** 2)

        y_ref = jax.jit(lambda p, x: spm_apply(p, x, ref_cfg))(p, x)
        g_ref = jax.jit(jax.grad(loss(ref_cfg), argnums=(0, 1)))(p, x)
        mesh = _mesh(shards)
        with activation_sharding(mesh, shard_feature=True):
            assert spm_shard.sharded_eligible(cfg_q)
            y_q = jax.jit(lambda p, x: spm_apply(p, x, cfg_q))(p, x)
            g_q = jax.jit(jax.grad(loss(cfg_q), argnums=(0, 1)))(p, x)
            if overlap:
                y_ser = jax.jit(
                    lambda p, x: spm_apply(p, x, cfg_ser_q))(p, x)

        bound = _coeff_quant_bound_l2(x, p, cfg_q)
        y_ref_l2 = float(jnp.linalg.norm(y_ref))
        err = float(jnp.linalg.norm(y_q - y_ref))
        assert err <= bound, (err, bound)
        # the bound must be meaningful: well below the signal itself, so
        # a wrong-scale / wrong-slab bug (error on the order of the
        # signal) trips the assertion above
        assert bound < 0.5 * y_ref_l2, (bound, y_ref_l2)
        if overlap:
            # same quantized tables, same quantization grouping: overlap
            # only re-blocks the rows, so it agrees with serial to a few
            # ulp of f32 reassociation — NOT within some quantization
            # bound (that would hide a grouping bug)
            np.testing.assert_allclose(np.asarray(y_q),
                                       np.asarray(y_ser),
                                       rtol=1e-5, atol=1e-6)
        # grads are STRAIGHT-THROUGH grads of the dequantized operator: a
        # multiplicatively ~eps_rel-perturbed J in g = 2 J^T y, so they
        # track the reference within the same relative bound (x8 headroom
        # for the two perturbed factors and sum-loss accumulation)
        eps_rel = bound / y_ref_l2
        for a, b in zip(jax.tree.leaves(g_q), jax.tree.leaves(g_ref)):
            atol = 8 * eps_rel * max(float(jnp.linalg.norm(b)), 1.0)
            assert float(jnp.linalg.norm(a - b)) <= atol

    def test_compressed_pod_convergence_char_lm():
        """ISSUE 9 acceptance: the char-LM training driver with
        ``compress_pod_grads=True`` on a real 8-device ("pod",) shard_map
        mesh converges within tolerance of the uncompressed pod run —
        int8 error-feedback gradient reduction changes bytes on the wire,
        not the training trajectory."""
        from repro.configs import get_smoke
        from repro.data.char_corpus import build_corpus
        from repro.launch.train import build_parser, make_batch_fn, train
        from repro.models import causal_lm as LM

        def run(compress):
            argv = ["--arch", "qwen3-1.7b", "--smoke", "--steps", "20",
                    "--batch", "8", "--seq", "32", "--pod-dp", "8",
                    "--log-every", "100"]
            if compress:
                argv.append("--compress-pod-grads")
            return train(build_parser().parse_args(argv))

        state_u = run(compress=False)
        state_c = run(compress=True)
        assert "ef" in state_c["opt"] and "ef" not in state_u["opt"]

        cfg = get_smoke("qwen3-1.7b")
        corpus = build_corpus(200_000, seed=0)
        batch = make_batch_fn(cfg, 32, corpus)(jax.random.PRNGKey(99), 16)
        loss_of = lambda st: float(LM.lm_loss(st["params"], batch,
                                              cfg)[0])
        init_p = __import__("repro.models.transformer",
                            fromlist=["init_model"]).init_model(
            jax.random.PRNGKey(0), cfg)
        l0 = float(LM.lm_loss(init_p, batch, cfg)[0])
        lu, lc = loss_of(state_u), loss_of(state_c)
        assert lu < l0 and lc < l0            # both actually trained
        # EF keeps the compressed trajectory tight to the uncompressed
        # one: same data, same init, only int8 grid noise on the reduce
        assert abs(lc - lu) <= 0.05 * lu, (lc, lu, l0)
