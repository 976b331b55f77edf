"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: layouts Mosaic cannot tile, scoped-VMEM overruns,
collective kernels without a barrier.  These tests compile, for a v5e
that is described and not attached, the fused SPM forward and backward
at every projection shape of qwen3-1.7b (plus its 2048 -> 4096 fused-qkv
width) with bf16 activations, at the row block the planner picks for a
train batch and for a decode tick, and the RDMA overlap kernel pair on a
4-chip ``("model",)`` mesh.  Each compiled program must hold its
``tpu_custom_call``.  A small qwen3 train step compiled with the kernels
on shows every kernel call, forward, recomputed and backward, under the
``spm`` scope (``repro.obs``).  Nothing runs; a passing compile is not a
chip run.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library, and every test worker imports
this file), and skips where it cannot be described.  The persistent
compilation cache is off around these compiles: an entry compiled for a
described chip cannot be read back without one.
"""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.linear import LinearConfig
from repro.kernels import ops
from repro.kernels import spm_stack as K

# qwen3-1.7b: d_model 2048, 16 query / 8 KV heads of 128, d_ff 6144
SITES = {"qkv": (2048, 4096), "q|o": (2048, 2048), "k|v": (2048, 1024),
         "gate|up": (2048, 6144), "down": (6144, 2048)}
TRAIN_ROWS, DECODE_ROWS = 2048, 4
COMPILE_LIMIT_S = 120


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_time_limit():
    """Fail a compile that stalls instead of holding up the suite.  The
    alarm's handler runs when control is next in Python, so a compile
    stuck inside the compiler fails as soon as it returns."""
    def _expire(signum, frame):
        raise TimeoutError(f"compile exceeded {COMPILE_LIMIT_S}s")

    old = signal.signal(signal.SIGALRM, _expire)
    signal.alarm(COMPILE_LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _site(name):
    d_in, d_out = SITES[name]
    scfg = LinearConfig(d_in=d_in, d_out=d_out,
                        impl="spm_general").spm_config()
    return d_in, d_out, scfg.n, scfg.pairing.strides()


def _operator_args(sharding, rows, d_in, n, strides):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (s((rows, d_in), jnp.bfloat16), s((len(strides), n // 2, 4)),
            s((n,)), s((n,)))


def _kernel_count(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("rows", [TRAIN_ROWS, DECODE_ROWS],
                         ids=["train_rows", "decode_rows"])
@pytest.mark.parametrize("name", list(SITES))
def test_fused_forward_compiles_for_v5e(one_chip, name, rows):
    d_in, d_out, n, strides = _site(name)

    def fwd(x, cf, din, dout):
        return ops.spm_stack_fused(x, cf, strides, d_in=din, d_out=dout,
                                   in_width=d_in, out_width=d_out,
                                   interpret=False)

    compiled = jax.jit(fwd).lower(
        *_operator_args(one_chip, rows, d_in, n, strides)).compile()
    runs = ops.plan_runs_for_rows(n, strides, rows, dtype_bytes=2)
    assert _kernel_count(compiled) == len(runs)


@pytest.mark.parametrize("name", list(SITES))
def test_fused_backward_compiles_for_v5e(one_chip, name):
    d_in, d_out, n, strides = _site(name)

    def loss(x, cf, din, dout):
        y = ops.spm_stack_fused(x, cf, strides, d_in=din, d_out=dout,
                                in_width=d_in, out_width=d_out,
                                interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *_operator_args(one_chip, TRAIN_ROWS, d_in, n, strides)).compile()
    runs = ops.plan_runs_for_rows(n, strides, TRAIN_ROWS, dtype_bytes=2)
    assert _kernel_count(compiled) == 2 * len(runs)   # fwd + bwd per run


# ---------------------------------------------------------------------------
# RDMA overlap pair on a 4-chip mesh
# ---------------------------------------------------------------------------

N, SHARDS, ROWS, BLOCK_ROWS = 2048, 4, 512, 128
N_LOCAL = N // SHARDS
LOCAL_STRIDES = (1, 2, 4, 8, 16, 32, 64, 128, 256)   # one local run


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices[:SHARDS]), ("model",))


def _partner():
    """XOR partner (cross stride k = 1) as the kernel's scalar-prefetch
    mesh coordinates."""
    return jnp.reshape(jax.lax.axis_index("model") ^ 1, (1,))


def _pair_shapes(mesh):
    act = NamedSharding(mesh, P(None, "model"))
    vec = NamedSharding(mesh, P("model"))
    rep = NamedSharding(mesh, P())
    L = len(LOCAL_STRIDES)
    return (jax.ShapeDtypeStruct((ROWS, N), jnp.float32, sharding=act),
            jax.ShapeDtypeStruct((L, N_LOCAL // 2, 4), jnp.float32,
                                 sharding=rep),
            jax.ShapeDtypeStruct((SHARDS, N_LOCAL), jnp.float32,
                                 sharding=vec),
            jax.ShapeDtypeStruct((SHARDS, N_LOCAL), jnp.float32,
                                 sharding=vec))


def test_rdma_overlap_forward_compiles_for_v5e(mesh):
    def body(x, cf, mix_a, mix_b):
        return K.spm_overlap_kernel_call(
            x, cf, mix_a[0], mix_b[0], _partner(), strides=LOCAL_STRIDES,
            block_rows=BLOCK_ROWS, n_tile=N_LOCAL, interpret=False)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, "model"), P(), P("model"),
                                P("model")),
                      out_specs=P(None, "model"), check_vma=False)
    compiled = jax.jit(f).lower(*_pair_shapes(mesh)).compile()
    assert _kernel_count(compiled) == 1


def test_rdma_overlap_backward_compiles_for_v5e(mesh):
    def body(x, cf, u, v):
        gx, gcf, s_own, s_swp = K.spm_overlap_bwd_kernel_call(
            x, cf, x, u[0], v[0], _partner(), strides=LOCAL_STRIDES,
            block_rows=BLOCK_ROWS, n_tile=N_LOCAL, interpret=False)
        return gx, gcf[None], (s_own + s_swp)[None]

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, "model"), P(), P("model"),
                                P("model")),
                      out_specs=(P(None, "model"), P("model"), P("model")),
                      check_vma=False)
    compiled = jax.jit(f).lower(*_pair_shapes(mesh)).compile()
    assert _kernel_count(compiled) == 1


def test_train_step_kernels_lie_under_the_spm_scope(one_chip, monkeypatch):
    """Every SPM kernel of a train step (stack and fused norm -> SPM
    block kernels; forward, remat recompute and backward) carries the
    ``spm`` scope in its ``op_name``, so a trace attributes it there."""
    import dataclasses
    import re

    from repro import obs
    from repro.configs import get_smoke
    from repro.models import causal_lm as LM
    from repro.models import transformer as T
    from repro.optim.adamw import OptimizerConfig
    from repro.train.state import make_train_state
    from repro.train.step import make_train_step

    cfg = dataclasses.replace(get_smoke("qwen3-1.7b", use_kernel=True),
                              d_model=256, d_ff=512, n_heads=2,
                              n_kv_heads=1, head_dim=128, vocab_size=512)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    # the fused norm -> SPM block path engages on a TPU backend only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    state = jax.eval_shape(lambda: make_train_state(
        T.init_model(jax.random.PRNGKey(0), cfg)))
    on_chip = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), t)
    batch = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(lambda p, b: LM.lm_loss(p, b, cfg),
                                   OptimizerConfig(), chaos_guard=True))
    text = step.lower(on_chip(state), batch, jax.ShapeDtypeStruct(
        (), jnp.float32, sharding=one_chip)).compile().as_text()
    calls = [re.search(r'op_name="([^"]*)"', ln).group(1)
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(obs.scope_of(n) == "spm" for n in calls), calls
    for kernel in ("spm_stack_kernel_call", "spm_stack_bwd_kernel_call",
                   "spm_block_kernel_call", "spm_block_bwd_kernel_call"):
        assert any(f"({kernel})" in n for n in calls), kernel
    assert any("rematted_computation" in n for n in calls)
