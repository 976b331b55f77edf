def read(ctx):
    return ctx.tr.scope_share(ctx.ops, 'moe', *ctx.window)
