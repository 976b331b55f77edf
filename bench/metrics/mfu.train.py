"""Model FLOP utilisation of training: the FLOPs a step's forward and
backward require per token (``work.train_step_flops``; recompute not
counted) times the window's tokens per second, over the chip's bf16
peak."""


def read(ctx):
    h = ctx.host
    per_token = ctx.work.train_step_flops(ctx.shape, h["batch"], h["seq"]) \
        / (h["batch"] * h["seq"])
    return 100.0 * per_token * h["tokens_per_s"] / ctx.peaks["peak_flops"]
