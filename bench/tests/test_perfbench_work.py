"""Work counts: the SPM sites counted are the model's linears, whatever
plan or kernel runs them."""

import dataclasses

import jax
import pytest

import tiny_cells  # noqa: F401
import work

from repro.configs import get_config
from repro.models import transformer as T

QWEN3_1_7B = {"hidden_size": 2048, "intermediate_size": 6144,
              "num_hidden_layers": 28, "num_attention_heads": 16,
              "num_key_value_heads": 8, "head_dim": 128,
              "vocab_size": 151936}


def _spm_leaves(cfg):
    tree = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    layer = tree["layers"]["l0"]
    out = {}
    for group in ("mixer", "mlp"):
        for name, p in layer[group].items():
            if isinstance(p, dict) and "mix" in p:
                G, L, half, four = p["mix"].shape
                out[name] = (G, L, 2 * half, p["d_in"].shape[-1],
                             sum(x.size for x in jax.tree.leaves(p)) // G)
    return out


@pytest.mark.parametrize("use_kernel", [None, True, False])
def test_counted_sites_are_the_models_linears(use_kernel):
    cfg = dataclasses.replace(get_config("qwen3-1.7b"),
                              spm_use_kernel=use_kernel)
    leaves = _spm_leaves(cfg)
    sites = {name: (a, b) for name, a, b in work.spm_sites(QWEN3_1_7B)}
    assert set(leaves) == set(sites)
    for name, (a, b) in sites.items():
        G, L, n, n_diag, count = leaves[name]
        assert G == QWEN3_1_7B["num_hidden_layers"]
        assert n == n_diag == work.spm_width(a, b)
        assert L == work.spm_stages(n)
        assert count == work.spm_param_count(a, b)


def test_counts_follow_shapes_only():
    f = work.spm_work(2048, 6144, 4096)
    n, L = 6144, 12
    assert f["flops"] == 4096 * n * (3 * L + 2)
    assert f["bytes"] == 4096 * (2048 + 6144) * 2 + (L * n // 2 * 4
                                                     + 2 * n) * 4
    b = work.spm_work(2048, 6144, 4096, backward=True)
    assert b["flops"] > 2 * f["flops"]
    # causal attention: query t sees t + 1 keys
    s = dict(QWEN3_1_7B, num_hidden_layers=1, num_attention_heads=1,
             head_dim=1)
    assert work.attention_flops(s, 3) == 4 * (1 + 2 + 3)
    assert work.attention_flops(s, 1, k_offset=9) == 4 * 10
    assert work.decode_flops(QWEN3_1_7B, 0) > work.head_flops(QWEN3_1_7B, 1)
