"""Share of the traced stretch of serving in which no operation ran on
the device: 1 - union of device op intervals / stretch."""


def read(ctx):
    lo, hi = ctx.window
    busy = sum(ctx.tr.busy_seconds(ops, lo, hi) for ops in ctx.ops) \
        / max(len(ctx.ops), 1)
    if hi <= lo or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
