"""Session: prefill wall time per thousand prompt tokens, over the
window's prefills (the program's ``prefill`` events)."""


def read(ctx):
    tokens = sum(lp for _, _, lp, _ in ctx.prefill)
    if not tokens:
        return None
    return sum(e - s for s, e, _, _ in ctx.prefill) / tokens * 1e6
