"""Device time under the program scope ``sample`` (the sampler:
temperature, top-k and the sorts over the vocabulary) over busy device
time, in the traced stretch of serving, prefill and decode ticks alike.
Each instant is charged to the innermost op running
(``trace_reduce.scope_share``); ``None`` where no op carries the scope."""

SCOPE = "sample"


def read(ctx):
    return ctx.tr.scope_share(ctx.ops, SCOPE, *ctx.window)
