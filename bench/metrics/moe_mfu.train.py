"""Model FLOP utilisation of training a mixture of experts: ``mfu.train``'s
definition with this model's work.  Per token, the attention and the LM
head three times their forward (``work.attention_flops``,
``work.head_flops``), the dense ``hidden_size -> num_experts`` router
three times its forward in every layer, and the SPM work of the attention
maps and of the ``num_experts_per_tok`` experts each token is routed to,
forward and backward (``moe_spm_roofline.train``'s count, the same
``work.spm_work`` terms); recompute and tile padding are not counted.
Times the window's tokens per second, over the chip's bf16 peak.
``None`` for a configuration without experts."""


def step_flops(work, shape, batch, seq):
    d, hd = shape["hidden_size"], shape["head_dim"]
    q = shape["num_attention_heads"] * hd
    kv = shape["num_key_value_heads"] * hd
    f, k = shape["moe_intermediate_size"], shape["num_experts_per_tok"]
    rows = batch * seq
    spm = 0.0
    for a, b, per_token in ((d, q, 1), (d, kv, 1), (d, kv, 1), (q, d, 1),
                            (d, f, k), (d, f, k), (f, d, k)):
        for backward in (False, True):
            spm += work.spm_work(a, b, rows * per_token, backward)["flops"]
    router = 2.0 * d * shape["num_experts"] * rows
    fwd_mm = (batch * work.attention_flops(shape, seq)
              + work.head_flops(shape, rows)
              + router * shape["num_hidden_layers"])
    return 3.0 * fwd_mm + spm * shape["num_hidden_layers"]


def read(ctx):
    if "num_experts" not in ctx.shape:
        return None
    h = ctx.host
    per_token = step_flops(ctx.work, ctx.shape, h["batch"], h["seq"]) \
        / (h["batch"] * h["seq"])
    return 100.0 * per_token * h["tokens_per_s"] / ctx.peaks["peak_flops"]
