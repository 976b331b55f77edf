"""Everything a run needs, found by name.

``BENCHMARK.json`` (root of the checkout) names each cell's configuration
and traffic mix.  A configuration is ``configs/<file>.json``: the
published ``config.json`` keys as run, the program's registry arch and
overrides that realise it, and the file of its plain reference; where
needed, the published keys no program field realises and the program
fields it requires (``program_config``).  A traffic mix is
``traffic/<mix>.json``; its ``kind`` picks the module ``kinds/<kind>.py``.
A per-layer metric is ``metrics/<metric>.py`` with a ``read(ctx)`` that
returns a number or ``None``.  The limits of a cell's correctness numbers
are ``limits/<cell>.json``.  Adding any of these is adding a file; no
file here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# published key -> program ModelConfig field, for keys every configuration
# states
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
}
# the same, for keys checked wherever a configuration states them
STATED_FIELDS = {
    "num_experts": "n_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    config_entry: dict    # its entry in BENCHMARK.json
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict
    bench_dir: str

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: List[dict], cell: str,
              e2e_names: Optional[set] = None) -> List[dict]:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m["moves"] in e2e_names:
            out.append(m)
    return out


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    centry = confs[w["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    e2e = _for_cell(bench["end_to_end"], workload)
    per_layer = _for_cell(bench["per_layer"], workload,
                          {m["name"] for m in e2e})
    limits_path = os.path.join(bench_dir, "limits", f"{workload}.json")
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_read_json(os.path.join(root, centry["file"])),
        config_entry=centry, traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        f"{w['traffic']}.json")),
        end_to_end=e2e, per_layer=per_layer,
        limits=(_read_json(limits_path) if os.path.exists(limits_path)
                else {}),
        bench_dir=bench_dir)


def load_module(path: str, name: Optional[str] = None):
    name = name or "bench_" + os.path.basename(path).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference(cell: Cell):
    """The configuration's plain reference module."""
    return load_module(os.path.join(cell.bench_dir, "configs",
                                    cell.config["reference"]))


def kind_module(cell: Cell):
    return load_module(os.path.join(cell.bench_dir, "kinds",
                                    f"{cell.kind}.py"))


def metric_reader(cell: Cell, name: str):
    return load_module(os.path.join(cell.bench_dir, "metrics",
                                    f"{name}.py")).read


def published(cell: Cell) -> dict:
    """The configuration's published keys as run (scalars only)."""
    return {k: v for k, v in cell.config.items()
            if isinstance(v, (int, float, str, bool))}


PROGRAM_KEYS = ("arch", "overrides", "require", "unmapped")


def field_map(cell: Cell) -> dict:
    """Published key -> program field, as ``program_config`` checks it:
    ``PROGRAM_FIELDS`` and the ``STATED_FIELDS`` the file states, less the
    file's ``program.unmapped`` (key -> why no program field realises
    it)."""
    prog = cell.config["program"]
    unknown = set(prog) - set(PROGRAM_KEYS)
    if unknown:
        raise ValueError(f"program keys {sorted(unknown)} unknown; "
                         f"known: {PROGRAM_KEYS}")
    unmapped = prog.get("unmapped", {})
    if not (isinstance(unmapped, dict) and all(
            isinstance(v, str) and v.strip() for v in unmapped.values())):
        raise ValueError(f"program.unmapped maps each published key to "
                         f"the reason no program field realises it; got "
                         f"{unmapped!r}")
    out = dict(PROGRAM_FIELDS)
    out.update((k, f) for k, f in STATED_FIELDS.items()
               if k in cell.config)
    return {k: f for k, f in out.items() if k not in unmapped}


def program_config(cell: Cell):
    """The program's ModelConfig for this configuration: the registry
    arch with the file's overrides, checked against the published keys
    (``field_map``) and the file's ``program.require`` (program field ->
    value it must hold)."""
    from repro.configs import get_config
    prog = cell.config["program"]
    cfg = get_config(prog["arch"])
    over = dict(prog.get("overrides", {}))
    if "n_layers" in over:
        over["layers"] = tuple(cfg.layers[: over["n_layers"]])
    cfg = dataclasses.replace(cfg, **over)
    for key, field in field_map(cell).items():
        want = cell.config[key]
        got = getattr(cfg, field)
        if (float(got) if isinstance(want, float) else got) != want:
            raise ValueError(f"program config {field}={got!r} differs from "
                             f"the published {key}={want!r}")
    for field, want in prog.get("require", {}).items():
        got = getattr(cfg, field)
        if got != want:
            raise ValueError(f"program config {field}={got!r}; the "
                             f"configuration requires {want!r}")
    return cfg
