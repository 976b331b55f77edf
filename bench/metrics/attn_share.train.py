"""Device time under the program scope ``attn`` (the attention layer:
its kernels, their layout fusions and the glue around them) over busy
device time, in the traced training steps. Each instant is charged to
the innermost op running (``trace_reduce.scope_share``); ``None`` where
no op carries the scope."""

SCOPE = "attn"


def read(ctx):
    return ctx.tr.scope_share(ctx.ops, SCOPE, *ctx.window)
