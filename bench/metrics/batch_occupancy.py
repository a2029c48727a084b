"""Scheduler: mean share of the decode slots that were active, over the
window's decode steps (the program's ``decode`` events, ``n_active``)."""


def read(ctx):
    if not ctx.decode:
        return None
    return 100.0 * sum(n for _, _, n in ctx.decode) / (len(ctx.decode)
                                                        * ctx.slots)
