"""Cells, configurations, mixes and metrics are found by name: a new one
is a new file, and no existing file changes."""

import json
import os
import subprocess
import sys

import tiny_cells


def _files(root):
    out = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_new_config_mix_metric_and_cell_are_picked_up(tmp_path):
    import spec
    root = tiny_cells.make(str(tmp_path))
    before = _files(os.path.join(root, "bench"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny2.json"), "w") as f:
        json.dump(dict(tiny_cells.TINY, num_hidden_layers=1,
                       program={"arch": "qwen3-1.7b", "overrides": dict(
                           tiny_cells.TINY["program"]["overrides"],
                           n_layers=1)}), f)
    with open(os.path.join(b, "traffic", "tiny-train2.json"), "w") as f:
        json.dump(dict(tiny_cells.TRAIN, batch=4), f)
    with open(os.path.join(b, "metrics", "steps_seen.train.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.host['steps_traced']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "bench/configs/tiny2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2.train2", "config": "tiny2",
                               "traffic": "tiny-train2", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_seen.train", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny2.train2"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("tiny2.train2", root=root)
    assert cell.traffic["batch"] == 4
    assert spec.program_config(cell).n_layers == 1
    assert [m["name"] for m in cell.per_layer] == ["steps_seen.train"]
    assert spec.metric_reader(cell, "steps_seen.train")(
        type("C", (), {"host": {"steps_traced": 3}})) == 3
    assert spec.kind_module(cell).__name__.endswith("train_py")
    after = _files(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())


def test_published_keys_must_match_the_program(tmp_path):
    import pytest
    import spec
    root = tiny_cells.make(str(tmp_path), dict(tiny_cells.TINY,
                                               hidden_size=80))
    with pytest.raises(ValueError, match="hidden_size"):
        spec.program_config(spec.load_cell("tiny.train", root=root))


def _run_cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.train",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu_and_prints_no_result(tmp_path):
    root = tiny_cells.make(str(tmp_path))
    os.symlink(os.path.join(tiny_cells.ROOT, "src"),
               os.path.join(root, "src"))
    p = _run_cli(root)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    root = tiny_cells.make(str(tmp_path))
    p = _run_cli(root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
