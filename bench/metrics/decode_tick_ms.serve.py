"""Median wall time of a decode tick that carries no admit, over the
window."""

import statistics


def read(ctx):
    ticks = [d for d, admit in ctx.host["ticks"] if not admit]
    if not ticks:
        return None
    return 1e3 * statistics.median(ticks)
