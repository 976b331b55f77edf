"""Wall time of the engine's prefill calls (each waited for) over the
real prompt tokens they carried, per thousand tokens, over the window."""


def read(ctx):
    pf = ctx.host["prefills"]
    toks = sum(p[3] for p in pf)
    if not toks:
        return None
    return 1e3 * sum(p[0] for p in pf) / (toks / 1e3)
