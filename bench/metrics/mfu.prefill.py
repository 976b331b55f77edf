"""Model FLOP utilisation of serving: the FLOPs the prompts prefilled and
the tokens decoded while the profiler ran require (``work.prefill_flops``:
the LM head only at a prompt's last position, no bucket padding;
``work.decode_flops`` at each token's position), over the traced
stretch and the chip's bf16 peak."""


def read(ctx):
    h = ctx.host
    if not h["traced_s"]:
        return None
    flops = sum(ctx.work.prefill_flops(ctx.shape, n)
                for n in h["traced_prompt_lens"])
    flops += sum(ctx.work.decode_flops(ctx.shape, p)
                 for p in h["traced_decode_positions"])
    if flops <= 0:
        return None
    return 100.0 * flops / h["traced_s"] / ctx.peaks["peak_flops"]
