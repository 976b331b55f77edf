"""The causal attention kernel's share of its roofline in a training step:
the least time the chip needs for the attention work of the traced steps
(forward and backward counted as three times the forward, no recompute,
as ``mfu.train`` counts them; ``work.attention_flops``) at the bf16 matrix
peak, over the device time of the attention kernel's events (splash
attention's forward, dq and dkv kernels, by name).  ``None`` where no
such event ran: the chunked attention path has no kernel of its own."""

ATTN = r"splash_m[qh]a_(fwd|dq|dkv)"


def read(ctx):
    n = ctx.host["steps_traced"]
    attn_s = sum(ctx.tr.op_seconds(ctx.tr.matching(ops, ATTN))
                 for ops in ctx.ops) / max(len(ctx.ops), 1)
    if not n or attn_s <= 0:
        return None
    flops = 3.0 * ctx.host["batch"] * ctx.work.attention_flops(
        ctx.shape, ctx.host["seq"])
    return 100.0 * n * flops / ctx.peaks["peak_flops"] / attn_s
