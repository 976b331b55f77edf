"""Operations and bytes that the model's work requires, from shapes alone.

The counts follow the algorithm, not the plan or kernel that implements
it, so a change of tiling, fusion or kernel leaves them unchanged:

* An SPM linear ``d_in -> d_out`` runs over ``n = even(max(d_in, d_out))``
  lanes with ``L = min(ceil(log2 n), 12)`` stages.  Forward: each stage
  costs 3 operations per lane (two products, one sum), the two diagonal
  scales one each; x is read once, y written once, the parameters read
  once.  Backward: 7 operations per lane per stage (3 for the input
  cotangent, 4 for the coefficient products) and 4 for the diagonals; the
  output cotangent and x are read once, the input cotangent written once,
  the parameters read once and their gradients written once.
* Attention needs, for a query at position ``t``, ``4 * head_dim *
  (t + 1)`` operations per head (scores and weighted values over the
  ``t + 1`` keys it may see).
* The LM head needs ``2 * d * V`` per position whose logits are used.

``shape`` is the configuration's published dict (``hidden_size``,
``num_attention_heads`` ... as in its ``config.json``).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

ACT_BYTES = 2        # bf16 activations
PARAM_BYTES = 4      # f32 parameters


def spm_width(d_in: int, d_out: int) -> int:
    m = max(d_in, d_out)
    return m + (m % 2)


def spm_stages(n: int) -> int:
    return max(1, min(int(math.ceil(math.log2(max(n, 2)))), 12))


def spm_sites(shape: dict) -> List[Tuple[str, int, int]]:
    """The SPM linears of one decoder layer as (name, d_in, d_out)."""
    d = shape["hidden_size"]
    q = shape["num_attention_heads"] * shape["head_dim"]
    kv = shape["num_key_value_heads"] * shape["head_dim"]
    f = shape["intermediate_size"]
    return [("q", d, q), ("k", d, kv), ("v", d, kv), ("o", q, d),
            ("gate", d, f), ("up", d, f), ("down", f, d)]


def spm_param_count(d_in: int, d_out: int) -> int:
    n = spm_width(d_in, d_out)
    return spm_stages(n) * (n // 2) * 4 + 2 * n


def spm_work(d_in: int, d_out: int, rows: int,
             backward: bool = False) -> Dict[str, float]:
    """FLOPs and HBM bytes of one SPM linear call over ``rows`` rows."""
    n = spm_width(d_in, d_out)
    L = spm_stages(n)
    pbytes = spm_param_count(d_in, d_out) * PARAM_BYTES
    if not backward:
        return {"flops": rows * n * (3 * L + 2),
                "bytes": rows * (d_in + d_out) * ACT_BYTES + pbytes}
    return {"flops": rows * n * (7 * L + 4),
            "bytes": rows * (2 * d_in + d_out) * ACT_BYTES + 2 * pbytes}


def layer_spm_work(shape: dict, rows: int,
                   backward: bool = False) -> Dict[str, float]:
    tot = {"flops": 0.0, "bytes": 0.0}
    for _, a, b in spm_sites(shape):
        w = spm_work(a, b, rows, backward)
        tot["flops"] += w["flops"]
        tot["bytes"] += w["bytes"]
    return tot


def model_spm_work(shape: dict, rows: int,
                   backward: bool = False) -> Dict[str, float]:
    w = layer_spm_work(shape, rows, backward)
    L = shape["num_hidden_layers"]
    return {"flops": w["flops"] * L, "bytes": w["bytes"] * L}


def attention_flops(shape: dict, q_len: int, k_offset: int = 0) -> float:
    """Forward attention operations of ``q_len`` consecutive queries that
    start at position ``k_offset``, over all layers (causal)."""
    H, dh = shape["num_attention_heads"], shape["head_dim"]
    first, last = k_offset + 1, k_offset + q_len      # keys seen
    keys = (first + last) * q_len / 2
    return 4.0 * dh * H * keys * shape["num_hidden_layers"]


def head_flops(shape: dict, positions: int) -> float:
    return 2.0 * shape["hidden_size"] * shape["vocab_size"] * positions


def train_step_flops(shape: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward and backward, no
    recompute): matmul work three times its forward, SPM as counted."""
    rows = batch * seq
    fwd_mm = batch * attention_flops(shape, seq) + head_flops(shape, rows)
    spm = (model_spm_work(shape, rows)["flops"]
           + model_spm_work(shape, rows, backward=True)["flops"])
    return 3.0 * fwd_mm + spm


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """Required forward FLOPs of one prompt: its attention and SPM rows,
    and the LM head at the last position only."""
    return (attention_flops(shape, prompt_len)
            + model_spm_work(shape, prompt_len)["flops"]
            + head_flops(shape, 1))


def decode_flops(shape: dict, position: int) -> float:
    """Required FLOPs of one decoded token at ``position``."""
    return (attention_flops(shape, 1, k_offset=position)
            + model_spm_work(shape, 1)["flops"] + head_flops(shape, 1))
