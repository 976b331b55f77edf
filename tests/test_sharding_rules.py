"""PartitionSpec rule-table tests (no multi-device needed: specs are pure
metadata; a 1x1 mesh carries the axis names)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_smoke
from repro.launch.hlo_analysis import (V5E, collective_bytes,
                                       parse_shape_bytes, peaks,
                                       roofline_terms)
from repro.launch.specs import abstract_cache, abstract_state, input_specs
from repro.configs.shapes import SHAPES
from repro.models import transformer as T
from repro.parallel import sharding as SH


def tiny_mesh(axes=("data", "model")):
    shape = (1,) * len(axes)
    return Mesh(np.asarray(jax.devices()[:1]).reshape(shape), axes)


MESH = tiny_mesh()
MESH3 = tiny_mesh(("pod", "data", "model"))


def test_embedding_vocab_parallel():
    assert SH.param_spec("embed/table", 2, MESH) == P("model", "data")


def test_dense_col_vs_row_parallel():
    assert SH.param_spec("layers/l0/mixer/q/w", 2, MESH) == P("data", "model")
    assert SH.param_spec("layers/l0/mixer/o/w", 2, MESH) == P("model", "data")
    assert SH.param_spec("layers/l0/mlp/up/w", 2, MESH) == P("data", "model")
    assert SH.param_spec("layers/l0/mlp/down/w", 2, MESH) == P("model", "data")


def test_scan_stacking_pads_leading_none():
    # scanned models stack a group axis in front: rules are trailing-dim
    assert SH.param_spec("layers/l0/mixer/q/w", 3, MESH) == \
        P(None, "data", "model")
    assert SH.param_spec("layers/l0/mixer/q/mix", 4, MESH) == \
        P(None, None, "model", None)


def test_spm_params_pair_parallel():
    assert SH.param_spec("layers/l0/mlp/up/mix", 3, MESH) == \
        P(None, "model", None)
    assert SH.param_spec("layers/l0/mixer/q/theta", 2, MESH) == \
        P(None, "model")
    assert SH.param_spec("layers/l0/mlp/up/d_in", 1, MESH) == P("model")


def test_expert_axis_gets_model():
    # scanned MoE: (G, E, d_in, d_ff)
    spec = SH.param_spec("layers/l0/mlp/experts/up/w", 4, MESH)
    assert spec == P(None, "model", "data", None)
    # expert SPM coeffs (G, E, L, pairs, 4): pairs must NOT reuse model
    spec = SH.param_spec("layers/l0/mlp/experts/up/mix", 5, MESH)
    assert spec == P(None, "model", None, None, None)


def test_spm_feat_profile_shard_splits_spm_params():
    """spm_feat: SPM stage coeffs split on the pair axis, diagonals/bias on
    the feature axis — the exact blocks parallel/spm_shard.py reads —
    while everything else keeps the spm_dp layout."""
    pf = "spm_feat"
    assert SH.param_spec("layers/l0/mlp/up/mix", 3, MESH, pf) == \
        P(None, "model", None)
    assert SH.param_spec("layers/l0/mixer/q/theta", 2, MESH, pf) == \
        P(None, "model")
    assert SH.param_spec("layers/l0/mlp/up/d_in", 1, MESH, pf) == P("model")
    assert SH.param_spec("layers/l0/mlp/up/bias", 1, MESH, pf) == P("model")
    # scanned stacking axes stay replicated (trailing-dim rules)
    assert SH.param_spec("layers/l0/mlp/up/mix", 4, MESH, pf) == \
        P(None, None, "model", None)
    # expert parallelism still wins for expert-stacked SPM params
    assert SH.param_spec("layers/l0/mlp/experts/up/mix", 5, MESH, pf) == \
        P(None, "model", None, None, None)
    # non-SPM params keep the spm_dp layout
    assert SH.param_spec("embed/table", 2, MESH, pf) == P("model", None)
    assert SH.param_spec("layers/l0/norm1/scale", 1, MESH, pf) == P(None)
    assert SH.param_spec("layers/l0/mixer/q/w", 2, MESH, pf) == P(None, None)


def test_router_replicated_norm_replicated():
    assert SH.param_spec("layers/l0/mlp/router", 2, MESH) == P(None, None)
    assert SH.param_spec("layers/l0/norm1/scale", 1, MESH) == P(None)


def test_data_axes_multi_pod():
    assert SH.data_axes(MESH) == ("data",)
    assert SH.data_axes(MESH3) == ("pod", "data")
    assert SH.batch_spec(MESH3) == P(("pod", "data"))
    assert SH.batch_spec(MESH, seq_sharded=True) == P(None, "data")


def test_param_shardings_cover_whole_tree():
    cfg = get_smoke("qwen3-moe-30b-a3b")
    state = abstract_state(cfg)
    sh = SH.param_shardings(MESH, state["params"])
    n_params = len(jax.tree.leaves(state["params"]))
    n_specs = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_params == n_specs


def test_cache_specs_scanned_and_seq_sharded():
    cfg = get_smoke("qwen3-1.7b")
    cache = abstract_cache(cfg, 4, 64)
    sh = SH.cache_specs(MESH, cache)
    flat = jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert all(hasattr(s, "spec") for s in flat)
    # scanned cache: leading group axis replicated, heads on model
    k_sh = sh[jax.tree_util.SequenceKey] if False else None
    sh_seq = SH.cache_specs(MESH, cache, seq_sharded=True)
    specs = [s.spec for s in jax.tree.leaves(
        sh_seq, is_leaf=lambda x: hasattr(x, "spec"))]
    assert any("data" in str(s) for s in specs)


# ---------------------------------------------------------------------------
# launch/specs + hlo analysis units
# ---------------------------------------------------------------------------

def test_input_specs_per_kind():
    cfg = get_smoke("qwen3-1.7b")
    tr = input_specs(cfg, SHAPES["train_4k"])
    assert tr["tokens"].shape == (256, 4096) and "labels" in tr
    pf = input_specs(cfg, SHAPES["prefill_32k"])
    assert pf["tokens"].shape == (32, 32768) and "labels" not in pf
    dc = input_specs(cfg, SHAPES["decode_32k"])
    assert dc["tokens"].shape == (128,) and dc["index"].shape == ()
    vl = get_smoke("qwen2-vl-7b")
    pv = input_specs(vl, SHAPES["prefill_32k"])
    assert pv["embeds"].shape == (32, 32768, vl.d_model)
    assert pv["positions"].shape == (3, 32, 32768)


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert parse_shape_bytes("bf16[2,3]") == 12
    assert parse_shape_bytes("(f32[8], s32[4])") == 32 + 16
    assert parse_shape_bytes("pred[]") == 1


def test_collective_bytes_parsing():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag = bf16[64,32]{1,0} all-gather(bf16[8,32]{1,0} %y), dimensions={0}
  %cp = f32[16]{0} collective-permute(f32[16]{0} %z)
  %no = f32[99]{0} add(f32[99]{0} %a, f32[99]{0} %b)
"""
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 4096
    assert cb["all-gather"] == 64 * 32 * 2
    assert cb["collective-permute"] == 64
    assert cb["total"] == 4096 + 4096 + 64


def test_roofline_terms_dominance():
    v5e = peaks(V5E)
    t = roofline_terms(197e12, 0.0, 0.0, v5e)   # 1s of pure compute
    assert t["dominant"] == "compute_s"
    assert t["roofline_fraction"] == pytest.approx(1.0)
    t = roofline_terms(1e12, 819e9 * 2, 0.0, v5e)   # memory-bound
    assert t["dominant"] == "memory_s"
    assert t["roofline_fraction"] < 0.01


def test_peaks_keyed_by_device_kind():
    """The v5e row carries the published Google Cloud "TPU v5e" peaks
    under the device_kind JAX reports, and a device that is not in the
    table raises instead of silently modeling v5e."""
    v5e = peaks("TPU v5 lite")
    assert (v5e["peak_flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
