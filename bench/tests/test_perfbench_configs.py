"""A configuration beyond dense Qwen3 enters as new files: the keys that
no program field realises, each with why, are data in its own file; its
expert keys are checked as stated.  The two real
configurations' weights do not change."""

import copy
import hashlib
import json
import os

import pytest

import tiny_cells
import spec
import weights


def _files(root):
    out = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def _structure(cell, shrink=None):
    import jax
    from repro.models import transformer as T
    cfg = spec.program_config(cell)
    st = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    if shrink:
        st = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            tuple(min(d, shrink) for d in s.shape), s.dtype), st)
    return st


def _add_moe(root, config):
    """Adds the MoE configuration, a training mix, a reader of the
    ``moe`` scope and a cell, each as a new file or entry."""
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-moe.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(b, "traffic", "tiny-moe-train.json"), "w") as f:
        json.dump(tiny_cells.TRAIN, f)
    with open(os.path.join(b, "metrics", "moe_share.train.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.tr.scope_share(ctx.ops, 'moe', *ctx.window)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-moe.train", "config": "tiny-moe",
                               "traffic": "tiny-moe-train", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "moe_share.train", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "model step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny-moe.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return spec.load_cell("tiny-moe.train", root=root)


def test_moe_configuration_enters_as_new_files(tmp_path):
    import numpy as np
    import trace_reduce as T
    root = tiny_cells.make(str(tmp_path))
    before = _files(os.path.join(root, "bench"))
    cell = _add_moe(root, tiny_cells.TINY_MOE)
    cfg = spec.program_config(cell)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff, cfg.d_ff) == (8, 2, 32, 0)
    assert all(s.mlp == "moe" for s in cfg.layers) and cfg.qk_norm
    p = weights.make_params(_structure(cell), 11)
    router = np.asarray(p["layers"]["l0"]["mlp"]["router"])
    assert router.shape == (2, 64, 8)
    assert 0.015 < router.std() < 0.025 and abs(router.mean()) < 0.005
    assert [m["name"] for m in cell.per_layer] == ["moe_share.train"]
    read = spec.metric_reader(cell, "moe_share.train")
    ctx = type("C", (), {"tr": T, "window": (0.0, 4.0), "ops": [[
        T.Span("fusion", 0.0, 1.0, scope="moe"), T.Span("spm", 1.0, 4.0,
                                                        scope="spm")]]})
    assert read(ctx) == pytest.approx(25.0)
    after = _files(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())


def test_leaf_without_a_rule_is_an_error():
    import jax
    import jax.numpy as jnp
    st = {"table": jax.ShapeDtypeStruct((4, 8), jnp.float32),
          "layers": {"ssm": {"A_log": jax.ShapeDtypeStruct((4,),
                                                           jnp.float32)}}}
    with pytest.raises(KeyError, match="A_log"):
        weights.make_params(st, 11)


@pytest.mark.parametrize("program", [
    {"unmapped": {"intermediate_size": ""}},
    {"unmapped": {"intermediate_size": None}},
    {"unmapped": ["intermediate_size"]},
    {"fields": {"intermediate_size": "moe_d_ff"}},
])
def test_malformed_key_map_is_an_error(tmp_path, program):
    conf = copy.deepcopy(tiny_cells.TINY_MOE)
    conf["program"].update(program)
    cell = _add_moe(tiny_cells.make(str(tmp_path)), conf)
    with pytest.raises(ValueError, match="program"):
        spec.program_config(cell)


def test_key_left_unmapped_needs_its_reason(tmp_path):
    conf = copy.deepcopy(tiny_cells.TINY_MOE)
    del conf["program"]["unmapped"]["intermediate_size"]
    cell = _add_moe(tiny_cells.make(str(tmp_path)), conf)
    with pytest.raises(ValueError, match="intermediate_size"):
        spec.program_config(cell)


@pytest.mark.parametrize("moe", [False, True])
def test_expert_keys_are_checked_where_stated(tmp_path, moe):
    root = tiny_cells.make(str(tmp_path))
    cell = (_add_moe(root, tiny_cells.TINY_MOE) if moe
            else spec.load_cell("tiny.train", root=root))
    got = spec.field_map(cell)
    assert {k: got.get(k) for k in spec.STATED_FIELDS} == (
        spec.STATED_FIELDS if moe else dict.fromkeys(spec.STATED_FIELDS))
    assert ("intermediate_size" in got) != moe


@pytest.mark.parametrize("key, value", [
    ("num_experts", 16), ("num_experts_per_tok", 4),
    ("moe_intermediate_size", 64), ("tie_word_embeddings", True)])
def test_contradicted_published_key_raises(tmp_path, key, value):
    cell = _add_moe(tiny_cells.make(str(tmp_path)),
                    dict(tiny_cells.TINY_MOE, **{key: value}))
    with pytest.raises(ValueError, match=key):
        spec.program_config(cell)


def test_required_program_field_is_held(tmp_path):
    conf = copy.deepcopy(tiny_cells.TINY)
    conf["program"]["overrides"]["qk_norm"] = False
    root = tiny_cells.make(str(tmp_path), conf)
    with pytest.raises(ValueError, match="qk_norm"):
        spec.program_config(spec.load_cell("tiny.train", root=root))
    for name in ("qwen3-1.7b", "qwen3-32b"):
        with open(os.path.join(spec.BENCH, "configs", f"{name}.json")) as f:
            assert json.load(f)["program"]["require"] == {"qk_norm": True}


# sha256 of each real configuration's weights from seed 2166136261, every
# dimension cut to at most 8, as the generator made them before the
# router had a rule
WEIGHT_HASHES = {
    "qwen3-1.7b.train-4k":
        "e3f7f448d31406d27729fec79e00a0371774f98b69404e267a7d86ae4eab2196",
    "qwen3-32b.serve-prefill":
        "867b7d923e307aae8c0bc229973be53bf005f36e631309fb0d542eca105bc5c1",
}


@pytest.mark.parametrize("workload", sorted(WEIGHT_HASHES))
def test_real_configurations_weights_are_unchanged(workload):
    import jax
    import numpy as np
    cell = spec.load_cell(workload)
    p = weights.make_params(_structure(cell, shrink=8), 2166136261)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(p):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHT_HASHES[workload]
