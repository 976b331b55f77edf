"""Documentation system checks (ISSUE 4 satellite).

Three guarantees, all cheap enough for every CI run:

* **docstring coverage** — every public symbol (``__all__``, else
  non-underscore module attributes) of the public API surface modules has
  a non-empty docstring, as does every public method of public classes
  defined in those modules;
* **README snippets execute** — every ```python fenced block in README.md
  runs top-to-bottom in one shared namespace (doctest-style: the blocks
  are written to be cumulative and assert their own claims);
* **no dead links** — every relative markdown link target in README.md
  and docs/*.md exists on disk (http(s) links are skipped: CI has no
  business depending on the network).
"""

import inspect
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

API_MODULES = [
    "repro.core.spm",
    "repro.core.linear",
    "repro.core.eligibility",
    "repro.configs.base",
    "repro.parallel",
    "repro.serve.engine",
    "repro.kernels.quant",
    "repro.optim.compression",
    "repro.obs",
]

DOC_FILES = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md")) if os.path.isdir(os.path.join(REPO, "docs")) \
    else ["README.md"]


@pytest.mark.parametrize("mod_name", API_MODULES)
def test_public_api_has_docstrings(mod_name):
    mod = __import__(mod_name, fromlist=["_"])
    assert inspect.getdoc(mod), f"{mod_name} has no module docstring"
    names = getattr(mod, "__all__", None) or [
        n for n in dir(mod) if not n.startswith("_")]
    missing = []
    for name in names:
        obj = getattr(mod, name)
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue
        if not inspect.getdoc(obj):
            missing.append(name)
        if inspect.isclass(obj):
            for mname, meth in inspect.getmembers(obj, inspect.isfunction):
                if mname.startswith("_"):
                    continue
                if not inspect.getdoc(meth):
                    missing.append(f"{name}.{mname}")
            for pname, prop in inspect.getmembers(
                    obj, lambda o: isinstance(o, property)):
                if not pname.startswith("_") and not inspect.getdoc(prop):
                    missing.append(f"{name}.{pname} (property)")
    assert not missing, f"{mod_name}: undocumented public symbols {missing}"


def _python_blocks(md_path):
    text = open(md_path).read()
    return re.findall(r"```python\n(.*?)```", text, flags=re.S)


def test_readme_snippets_execute():
    blocks = _python_blocks(os.path.join(REPO, "README.md"))
    assert blocks, "README.md has no ```python snippets"
    ns = {}
    for i, block in enumerate(blocks):
        try:
            exec(compile(block, f"README.md[python block {i}]", "exec"), ns)
        except Exception as e:   # pragma: no cover - failure path
            raise AssertionError(
                f"README python block {i} failed: {e}\n{block}") from e


def test_markdown_links_resolve():
    link_re = re.compile(r"\[[^\]]*\]\(([^)#\s]+)[^)]*\)")
    dead = []
    for rel in DOC_FILES:
        path = os.path.join(REPO, rel)
        base = os.path.dirname(path)
        for target in link_re.findall(open(path).read()):
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            cand = (os.path.join(REPO, target) if target.startswith("/")
                    else os.path.join(base, target))
            if not os.path.exists(cand):
                dead.append(f"{rel} -> {target}")
    assert not dead, f"dead markdown links: {dead}"


def test_readme_has_generated_results_table():
    """The results table between the BENCH-TABLE markers is generated from
    BENCH_kernel.json by benchmarks/readme_table.py — assert the markers
    exist and the block between them is non-trivial (regenerating it
    verbatim in CI would couple the test to bench reruns; the generator
    itself is exercised here instead)."""
    import importlib.util
    readme = open(os.path.join(REPO, "README.md")).read()
    start = "<!-- BENCH-TABLE:START (benchmarks/readme_table.py) -->"
    end = "<!-- BENCH-TABLE:END -->"
    assert start in readme and end in readme
    block = readme.split(start, 1)[1].split(end, 1)[0]
    assert block.count("|") > 20, "results table looks empty"
    spec = importlib.util.spec_from_file_location(
        "readme_table", os.path.join(REPO, "benchmarks", "readme_table.py"))
    rt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rt)
    import json
    with open(os.path.join(REPO, "BENCH_kernel.json")) as f:
        rendered = rt.render(json.load(f))
    for needle in ("reduction", "permute bytes", "| n |"):
        assert needle in rendered
