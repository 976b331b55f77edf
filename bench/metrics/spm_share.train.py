"""Device time of SPM kernel events over busy device time, in the traced
training steps."""

SPM = r"spm"


def read(ctx):
    lo, hi = ctx.window
    busy = sum(ctx.tr.busy_seconds(ops, lo, hi) for ops in ctx.ops)
    spm = sum(ctx.tr.busy_seconds(ctx.tr.matching(ops, SPM), lo, hi)
              for ops in ctx.ops)
    if busy <= 0 or spm <= 0:
        return None
    return 100.0 * spm / busy
